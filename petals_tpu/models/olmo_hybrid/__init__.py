from petals_tpu.models.olmo_hybrid.block import FAMILY as _BLOCK_FAMILY  # noqa: F401
from petals_tpu.models.olmo_hybrid.model import FAMILY as _FAMILY  # noqa: F401
from petals_tpu.models.olmo_hybrid.config import OlmoHybridBlockConfig

__all__ = ["OlmoHybridBlockConfig"]
