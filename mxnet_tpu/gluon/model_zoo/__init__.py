"""Gluon model zoo (ref: python/mxnet/gluon/model_zoo/__init__.py)."""
from . import vision
from . import nemotron_h
from . import laguna
from . import evabyte
from . import joyai
from . import lfm2
from . import ouro
from . import phi4flash
from .vision import get_model

__all__ = ["vision", "nemotron_h", "laguna", "evabyte", "joyai", "lfm2",
           "ouro", "phi4flash", "get_model"]
