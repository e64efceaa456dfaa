from petals_tpu.models.xing4_0.block import FAMILY as _BLOCK_FAMILY  # noqa: F401
from petals_tpu.models.xing4_0.model import FAMILY as _FAMILY  # noqa: F401
from petals_tpu.models.xing4_0.config import Xing40BlockConfig

__all__ = ["Xing40BlockConfig"]
