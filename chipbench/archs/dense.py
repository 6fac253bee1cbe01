"""Dense decoder: RMSNorm, rotary positions, grouped-query attention and
a gated-SiLU MLP in every layer (SmolLM, Granite).  The interface of
``archs/__init__.py`` over ``model.py``, ``reference.py`` and
``flops.py``, which compute it."""

from chipbench.flops import mlp_launches as fused_launches  # noqa: F401
from chipbench.flops import model_flops  # noqa: F401
from chipbench.model import Dims, make_weights, program_config  # noqa: F401
from chipbench.reference import logits  # noqa: F401

dims = Dims.of
