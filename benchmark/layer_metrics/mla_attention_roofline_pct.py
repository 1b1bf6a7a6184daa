"""Layer: kernels.  The latent cores' analytic FLOP floor over their
device time: the two products over exactly the causal pairs at the
published 192 (scores) + 128 (values) a head, forward and backward, no
recomputation (`latent_attention_flops_per_sample` in the
configuration's model.py) at the chip's bfloat16 peak, over
`mla_attention_device_ms`.  FLOP-bound by construction.  The kernels
visit whole blocks of the triangle, multiply 192 as the MXU's 256, and
the split backward forms the scores in both its kernels: a kernel at
peak reads well under 100% (PERF.md section 3)."""
from harness import lookup, scope_time

CELL = "joyai_llm_flash_s8192"


def read(run):
    ms = scope_time.op_ms(run, "latent_attention")
    if not ms:
        return None
    cell = lookup.cell(CELL)
    flops = (cell.model.latent_attention_flops_per_sample(
        cell.config, cell.traffic) * run["samples_per_step"])
    floor_s = flops / (run["chips"] * run["peak"].flops_bf16)
    return 100.0 * floor_s / (ms / 1e3)
