"""Model configuration schema shared by all assigned architectures."""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from .attention import AttentionConfig, MLAConfig
from .moe import MoEConfig
from .rwkv import RWKVConfig
from .ssm import MambaConfig


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One layer inside the repeating block pattern.

    kind:   'attn' | 'mamba' | 'rwkv'
    mlp:    'mlp' (dense, uses cfg.act/d_ff) | 'moe' | 'rwkv_cm' | 'none'
    window: sliding-window override for this layer (None = cfg default;
            used by Gemma2 local/global alternation).
    """

    kind: str = "attn"
    mlp: str = "mlp"
    window: int | None = None
    full_attention: bool = True      # False => use `window`


DENSE_LAYER = LayerSpec(kind="attn", mlp="mlp")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """``n_layers`` counts the layers of the uniform period stack; the
    ``first_k_dense`` leading dense layers (attention + dense MLP of
    ``d_ff``, DeepSeek's ``first_k_dense_replace``) come ahead of it."""

    name: str
    n_layers: int
    d_model: int
    vocab_size: int
    d_ff: int
    attn: AttentionConfig | None = None
    moe: MoEConfig | None = None
    mamba: MambaConfig | None = None
    rwkv: RWKVConfig | None = None
    pattern: tuple[LayerSpec, ...] = (LayerSpec(),)
    act: str = "silu"                # dense MLP activation ('gelu_tanh' => GeGLU)
    norm_eps: float = 1e-6
    zero_centered_norm: bool = False # Gemma-style (1 + w) RMSNorm
    post_norms: bool = False         # Gemma2 sandwich norms
    tie_embeddings: bool = False
    logit_softcap: float | None = None
    embed_scale: bool = False        # Gemma multiplies embeddings by sqrt(d)
    # modality / heads
    n_codebooks: int = 1             # MusicGen: parallel codebook streams
    prefix_len: int = 0              # VLM/audio stub: prepended frontend embeddings
    mtp_depth: int = 0               # DeepSeek-V3 multi-token prediction heads
    first_k_dense: int = 0           # leading dense layers ahead of the stack
    # dtypes
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    # citation for the config values
    source: str = ""

    # ------------------------------------------------------------------
    @property
    def n_periods(self) -> int:
        assert self.n_layers % len(self.pattern) == 0, (self.n_layers, len(self.pattern))
        return self.n_layers // len(self.pattern)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # -- analytic sizes (used by the Asteroid profiler/planner) ----------
    def layer_param_count(self, spec: LayerSpec) -> int:
        d = self.d_model
        n = 0
        if spec.kind == "attn" and self.attn is not None:
            a = self.attn
            if a.mla is not None:
                m = a.mla
                qk = m.qk_nope_dim + m.qk_rope_dim
                if m.q_lora_rank is None:
                    n += d * a.n_heads * qk
                else:
                    n += d * m.q_lora_rank + m.q_lora_rank * a.n_heads * qk
                n += d * (m.kv_lora_rank + m.qk_rope_dim)
                n += m.kv_lora_rank * a.n_heads * (m.qk_nope_dim + m.v_head_dim)
                n += a.n_heads * m.v_head_dim * d
            else:
                n += d * a.n_heads * a.head_dim * 2
                n += d * a.n_kv_heads * a.head_dim * 2
        elif spec.kind == "mamba" and self.mamba is not None:
            di = self.mamba.d_inner(d)
            dtr = self.mamba.get_dt_rank(d)
            n += d * 2 * di + self.mamba.d_conv * di
            n += di * (dtr + 2 * self.mamba.d_state) + dtr * di + di * d
        elif spec.kind == "rwkv" and self.rwkv is not None:
            n += 4 * d * d + d * d  # r,k,v,g,out
            n += d * self.rwkv.decay_lora + self.rwkv.decay_lora * d
            n += 5 * d * self.rwkv.mix_lora * 2
        if spec.mlp == "mlp":
            n += 3 * d * self.d_ff
        elif spec.mlp == "moe" and self.moe is not None:
            n += d * self.moe.router_width
            n += self.moe.n_experts * 3 * d * self.moe.d_ff
            n += self.moe.n_shared_experts * 3 * d * self.moe.d_ff
        elif spec.mlp == "rwkv_cm":
            n += d * self.d_ff + self.d_ff * d + d * d
        n += 2 * d  # norms
        return n

    def layer_active_param_count(self, spec: LayerSpec) -> int:
        """Params touched per token (MoE: the held share of top_k routed
        experts, top_k * held / routed, beside the shared experts)."""
        if spec.mlp != "moe" or self.moe is None:
            return self.layer_param_count(spec)
        m = self.moe
        expert = 3 * self.d_model * m.d_ff
        n = self.layer_param_count(spec) - m.n_experts * expert
        return n + m.top_k * m.n_experts * expert // m.router_width

    def _count(self, per_layer) -> int:
        n = sum(per_layer(s) for s in self.pattern) * self.n_periods
        n += per_layer(DENSE_LAYER) * self.first_k_dense
        n += self.vocab_size * self.d_model * self.n_codebooks * (1 if self.tie_embeddings else 2)
        return n

    def param_count(self) -> int:
        return self._count(self.layer_param_count)

    def active_param_count(self) -> int:
        return self._count(self.layer_active_param_count)
