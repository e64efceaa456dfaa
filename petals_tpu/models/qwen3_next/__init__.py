from petals_tpu.models.qwen3_next.block import FAMILY as _BLOCK_FAMILY  # noqa: F401
from petals_tpu.models.qwen3_next.model import FAMILY as _FAMILY  # noqa: F401
from petals_tpu.models.qwen3_next.config import Qwen3NextBlockConfig

__all__ = ["Qwen3NextBlockConfig"]
