from petals_tpu.models.longcat_flash.block import FAMILY as _BLOCK_FAMILY  # noqa: F401
from petals_tpu.models.longcat_flash.model import FAMILY as _FAMILY  # noqa: F401
from petals_tpu.models.longcat_flash.config import LongcatFlashBlockConfig

__all__ = ["LongcatFlashBlockConfig"]
