"""The Mellum2 decoder: attention that alternates sliding-window and full
layers (each with its own RoPE) and sparse experts in every layer, served
as one chip's share of an expert-parallel deployment.  Seeded weights
(``weights``), the program's parameter layout (``model``), the plain
reference (``reference``) and the operation counts (``flops``)."""

from . import flops
from .model import make_params, model_config
from .reference import control_gaps, served_gaps
from .weights import Dims

__all__ = ["Dims", "control_gaps", "flops", "make_params", "model_config",
           "served_gaps"]
