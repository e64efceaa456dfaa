from petals_tpu.models.olmoe.block import FAMILY as _BLOCK_FAMILY  # noqa: F401
from petals_tpu.models.olmoe.model import FAMILY as _FAMILY  # noqa: F401
from petals_tpu.models.olmoe.config import OlmoeBlockConfig

__all__ = ["OlmoeBlockConfig"]
