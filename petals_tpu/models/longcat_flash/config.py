"""``longcat_flash`` block config (``config.json`` as transformers'
``LongcatFlashConfig`` reads it; the reference has no such family).

A BLOCK here is one ``model.layers.{i}`` of the checkpoint, a double layer:
the framework's ``num_hidden_layers`` is the published ``num_layers`` (28),
never the class's ``num_hidden_layers`` (56 = 2 x ``num_layers``, which
transformers keeps for its own cache of two attentions a layer).

Two added keys say which share of each block's FFN experts a server holds, as
``exaone_moe``'s do: ``n_routed_experts`` is what its model directory HOLDS,
and ``expert_share: {"routed": 512, "first": 0}`` gives how many FFN experts
EXIST and which of them the first held one is. The router is wider than
either: ``routed + zero_expert_num`` outputs, the last ``zero_expert_num`` of
them identity experts, held nowhere and computed everywhere."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class LongcatFlashBlockConfig:
    hidden_size: int
    num_attention_heads: int
    q_lora_rank: int  # the query's low rank: q_a_proj, a norm, q_b_proj
    kv_lora_rank: int  # the latent a position caches, once an attention
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    head_dim: int  # the rotary's width (what the cache holds is two rows of ``kv_lora_rank + qk_rope_head_dim`` a position)
    ffn_hidden_size: int  # each of the block's two dense feed-forwards
    expert_ffn_hidden_size: int  # one expert's width
    num_hidden_layers: int  # BLOCKS: the published ``num_layers``
    num_experts: int  # FFN experts held on this server
    num_experts_exist: int  # FFN experts the model has (``expert_share.routed``; the router adds the identities)
    first_expert: int  # which of them the first held one is
    zero_expert_num: int  # identity experts: the router's last outputs
    moe_topk: int
    routed_scaling_factor: float
    rms_norm_eps: float  # the four block norms'
    rope_theta: float
    q_scale: float  # sqrt(hidden / q_lora_rank) where ``mla_scale_q_lora``, else 1
    kv_scale: float  # sqrt(hidden / kv_lora_rank) where ``mla_scale_kv_lora``, else 1
    vocab_size: int = 131072
    tie_word_embeddings: bool = False

    # q_a_layernorm and kv_a_layernorm are constructed without ``eps``: the class default, not ``rms_norm_eps``
    # (transformers 4.57.6 models/longcat_flash/modeling_longcat_flash.py:311, :319, :47)
    latent_norm_eps = 1e-6

    @property
    def router_width(self) -> int:
        return self.num_experts_exist + self.zero_expert_num

    @classmethod
    def from_hf_config(cls, hf_config) -> "LongcatFlashBlockConfig":
        get = lambda key, default=None: getattr(hf_config, key, default)
        # what the block does not compute is refused here, at load, not served wrong
        if get("rope_scaling"):
            # yarn rescales the frequencies and, through mscale, the softmax's scale
            raise NotImplementedError(f"longcat_flash: rope_scaling {get('rope_scaling')!r} is not supported (served: null)")
        if get("attention_bias", False):
            raise NotImplementedError("longcat_flash: attention_bias true is not supported (served: false, no bias on q_a, kv_a and o)")
        if get("router_bias", False):
            raise NotImplementedError("longcat_flash: router_bias true is not supported (served: false, the classifier has no bias)")
        if get("zero_expert_type", "identity") != "identity":
            raise NotImplementedError(
                f"longcat_flash: zero_expert_type {get('zero_expert_type')!r} is not supported (served: identity, a "
                f"zero-compute expert's output is the token itself)"
            )
        if get("hidden_act", "silu") != "silu":
            raise NotImplementedError(f"longcat_flash: hidden_act {get('hidden_act')!r} is not supported (served: silu)")
        if get("q_lora_rank") is None:
            raise NotImplementedError("longcat_flash: q_lora_rank null is not supported (served: a low-rank query, as the published class always computes)")
        for key in ("mla_scale_q_lora", "mla_scale_kv_lora"):
            if not get(key, True):
                raise NotImplementedError(f"longcat_flash: {key} false is not supported (served: true; transformers' class scales whatever the key says)")
        share = dict(get("expert_share") or {})
        held, exist, first = hf_config.n_routed_experts, share.get("routed", hf_config.n_routed_experts), share.get("first", 0)
        if not 0 <= first <= first + held <= exist:
            raise ValueError(f"longcat_flash: experts [{first}, {first + held}) are not among the {exist} that exist")
        return cls(
            hidden_size=hf_config.hidden_size,
            num_attention_heads=hf_config.num_attention_heads,
            q_lora_rank=hf_config.q_lora_rank,
            kv_lora_rank=hf_config.kv_lora_rank,
            qk_nope_head_dim=hf_config.qk_nope_head_dim,
            qk_rope_head_dim=hf_config.qk_rope_head_dim,
            v_head_dim=hf_config.v_head_dim,
            head_dim=hf_config.qk_rope_head_dim,
            ffn_hidden_size=hf_config.ffn_hidden_size,
            expert_ffn_hidden_size=hf_config.expert_ffn_hidden_size,
            num_hidden_layers=hf_config.num_layers,
            num_experts=held,
            num_experts_exist=exist,
            first_expert=first,
            zero_expert_num=int(get("zero_expert_num", 0) or 0),
            moe_topk=hf_config.moe_topk,
            routed_scaling_factor=float(get("routed_scaling_factor", 1.0)),
            rms_norm_eps=hf_config.rms_norm_eps,
            rope_theta=float(get("rope_theta", 10000000.0)),
            q_scale=(hf_config.hidden_size / hf_config.q_lora_rank) ** 0.5,
            kv_scale=(hf_config.hidden_size / hf_config.kv_lora_rank) ** 0.5,
            vocab_size=hf_config.vocab_size,
            tie_word_embeddings=get("tie_word_embeddings", False),
        )
