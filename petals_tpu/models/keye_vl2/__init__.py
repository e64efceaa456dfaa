from petals_tpu.models.keye_vl2.block import FAMILY as _BLOCK_FAMILY  # noqa: F401
from petals_tpu.models.keye_vl2.model import FAMILY as _FAMILY  # noqa: F401
from petals_tpu.models.keye_vl2.config import KeyeVL2BlockConfig

__all__ = ["KeyeVL2BlockConfig"]
