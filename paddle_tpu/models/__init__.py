"""paddle_tpu.models — flagship model families (functional SPMD cores).

Reference counterpart: the PaddleNLP / PaddleClas ecosystem models named by
BASELINE configs (ERNIE/BERT pretraining, LLaMA with sharding+TP; SURVEY.md
§2.4). These are the pure-functional, mesh-sharded training cores; the
eager/Layer-API model zoo lives in ``paddle_tpu.vision.models`` and the
``paddle_tpu.nn`` transformer layers.
"""

from . import bert  # noqa: F401
from . import hybrid_moe  # noqa: F401
from . import latent_moe  # noqa: F401
from . import llama  # noqa: F401
from . import power_retention  # noqa: F401

__all__ = ["bert", "llama", "latent_moe", "power_retention", "hybrid_moe",
           "family_of", "require"]


def family_of(cfg):
    """The module that implements ``cfg``'s decoder family — the model
    seam of the serving engine (``inference/serving.py``), which asks it
    for ``init_paged_pool``, ``page_bytes``, ``paged_kernel_active`` and
    ``forward_with_pages`` (and builds weights with its ``init_params``).
    One of four: ``llama`` (K / V row pages), ``latent_moe`` (latent row
    pages, routed experts on a share), ``power_retention`` (a sequence's
    recurrent state as its one page) and ``hybrid_moe`` (row pages for its
    full-attention layers beside a fixed part a sequence for its window
    layers — the one module that declares ``fixed_part_bytes`` — and
    ``latent_moe``'s experts)."""
    if isinstance(cfg, latent_moe.LatentMoEConfig):
        return latent_moe
    if isinstance(cfg, hybrid_moe.HybridMoEConfig):
        return hybrid_moe
    if isinstance(cfg, power_retention.PowerRetentionConfig):
        return power_retention
    return llama


def require(cfg, family: str) -> None:
    """Refuse, by name, a serving family that ``cfg``'s model is not
    served by (its module's ``SERVING_FAMILIES``)."""
    mod = family_of(cfg)
    if family not in mod.SERVING_FAMILIES:
        raise ValueError(
            f"{type(cfg).__name__} is not served by the {family!r} "
            f"family: {mod.__name__.rsplit('.', 1)[-1]} is served by "
            f"{', '.join(mod.SERVING_FAMILIES)}")
