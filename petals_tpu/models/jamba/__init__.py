from petals_tpu.models.jamba.block import FAMILY as _BLOCK_FAMILY  # noqa: F401
from petals_tpu.models.jamba.model import FAMILY as _FAMILY  # noqa: F401
from petals_tpu.models.jamba.config import JambaBlockConfig

__all__ = ["JambaBlockConfig"]
