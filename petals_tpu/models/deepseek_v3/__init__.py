from petals_tpu.models.deepseek_v3.block import FAMILY as _BLOCK_FAMILY  # noqa: F401
from petals_tpu.models.deepseek_v3.model import FAMILY as _FAMILY  # noqa: F401
from petals_tpu.models.deepseek_v3.config import DeepseekV3BlockConfig

__all__ = ["DeepseekV3BlockConfig"]
