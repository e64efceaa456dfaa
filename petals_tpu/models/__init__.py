from petals_tpu.models.registry import get_family, register_family

# Importing a family module registers it.
import petals_tpu.models.bloom  # noqa: F401
import petals_tpu.models.llama  # noqa: F401
import petals_tpu.models.falcon  # noqa: F401
import petals_tpu.models.mixtral  # noqa: F401
import petals_tpu.models.qwen2  # noqa: F401
import petals_tpu.models.mistral  # noqa: F401
import petals_tpu.models.gemma  # noqa: F401
import petals_tpu.models.phi3  # noqa: F401
import petals_tpu.models.gemma2  # noqa: F401
import petals_tpu.models.olmoe  # noqa: F401
import petals_tpu.models.exaone_moe  # noqa: F401
import petals_tpu.models.olmo_hybrid  # noqa: F401
import petals_tpu.models.keye_vl2  # noqa: F401
import petals_tpu.models.deepseek_v3  # noqa: F401
import petals_tpu.models.qwen3_next  # noqa: F401
import petals_tpu.models.jamba  # noqa: F401
import petals_tpu.models.longcat_flash  # noqa: F401
import petals_tpu.models.xing4_0  # noqa: F401
import petals_tpu.models.smallthinker  # noqa: F401

__all__ = ["get_family", "register_family"]
