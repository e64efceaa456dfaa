from petals_tpu.models.smallthinker.block import FAMILY as _BLOCK_FAMILY  # noqa: F401
from petals_tpu.models.smallthinker.model import FAMILY as _FAMILY  # noqa: F401
from petals_tpu.models.smallthinker.config import SmallThinkerBlockConfig

__all__ = ["SmallThinkerBlockConfig"]
