"""Keye-VL-2.0 language-model block config (``config.json`` of
Kwai-Keye/Keye-VL-2.0-30B-A3B, model_type ``KeyeVL2``; the installed
transformers has no class for it, so it is read as a plain
``PretrainedConfig``; the reference has no such family)."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class KeyeVL2BlockConfig:
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int  # one expert's width (moe_intermediate_size)
    num_hidden_layers: int
    num_experts: int
    num_experts_per_tok: int
    norm_topk_prob: bool
    rms_norm_eps: float
    rope_theta: float
    # sa_config: the indexer's heads, its head size (one key row of that a position) and how many positions a row keeps
    index_heads: int
    index_dim: int
    index_topk: int
    vocab_size: int = 151936
    tie_word_embeddings: bool = False

    @classmethod
    def from_hf_config(cls, hf_config) -> "KeyeVL2BlockConfig":
        get = lambda key, default=None: getattr(hf_config, key, default)
        # what the block does not compute is refused here, at load, not served wrong
        if get("hidden_act", "silu") != "silu":
            raise NotImplementedError(f"KeyeVL2: hidden_act {get('hidden_act')!r} is not supported (published: silu)")
        if get("attention_bias", False):
            raise NotImplementedError("KeyeVL2: attention_bias true is not supported (published: false)")
        if get("decoder_sparse_step", 1) != 1 or get("mlp_only_layers"):
            raise NotImplementedError("KeyeVL2: a layer without experts is not supported (published: every layer has them)")
        if get("use_sliding_window", False):
            raise NotImplementedError("KeyeVL2: use_sliding_window true is not supported (published: false)")
        rope = dict(get("rope_scaling") or {})
        if rope.get("rope_type", rope.get("type", "default")) != "default":
            # mrope_section splits the frequency pairs over three position ids; a server is sent one position a
            # row, the same in all three, which is the plain rotary (text; image tokens' ids are not served)
            raise NotImplementedError(f"KeyeVL2: rope_scaling {rope!r} is not supported (published: type default)")
        sa = dict(get("sa_config") or {})
        if not sa:
            raise ValueError("KeyeVL2: config.json has no sa_config (the indexer's sizes)")
        if sa.get("indexer_num_kv_heads", 1) != 1:
            raise NotImplementedError(
                f"KeyeVL2: indexer_num_kv_heads {sa['indexer_num_kv_heads']} is not supported (published: 1, one index key a position)"
            )
        return cls(
            hidden_size=hf_config.hidden_size,
            num_attention_heads=hf_config.num_attention_heads,
            num_key_value_heads=hf_config.num_key_value_heads,
            head_dim=get("head_dim") or hf_config.hidden_size // hf_config.num_attention_heads,
            intermediate_size=hf_config.moe_intermediate_size,
            num_hidden_layers=hf_config.num_hidden_layers,
            num_experts=hf_config.num_experts,
            num_experts_per_tok=hf_config.num_experts_per_tok,
            norm_topk_prob=bool(get("norm_topk_prob", False)),
            rms_norm_eps=hf_config.rms_norm_eps,
            rope_theta=float(get("rope_theta", 10000.0)),
            index_heads=int(sa["indexer_num_heads"]),
            index_dim=int(sa["indexer_head_dim"]),
            index_topk=int(sa["topk"]),
            vocab_size=hf_config.vocab_size,
            tie_word_embeddings=get("tie_word_embeddings", False),
        )
