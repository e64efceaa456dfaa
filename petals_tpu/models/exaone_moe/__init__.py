from petals_tpu.models.exaone_moe.block import FAMILY as _BLOCK_FAMILY  # noqa: F401
from petals_tpu.models.exaone_moe.model import FAMILY as _FAMILY  # noqa: F401
from petals_tpu.models.exaone_moe.config import ExaoneMoeBlockConfig

__all__ = ["ExaoneMoeBlockConfig"]
