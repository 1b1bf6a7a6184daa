"""Layer: kernels.  Device time per step on chip 0, forward, the forward
done again under remat, and backward, of the ops whose op scope is
`selective_scan`: the Mamba-1 scan's core in the three Mamba layers
(softplus and -exp, the relayouts in and out of the kernels' (8, 128)
channel registers, `mx_selective_scan_fwd` and `mx_selective_scan_bwd`,
the sums that finish dB, dC and dA; or, on the `chunked_xla` route, the
loop over chunks), not the projections, the convolution or the gate
around it."""
from harness import scope_time


def read(run):
    return scope_time.op_ms(run, "selective_scan")
