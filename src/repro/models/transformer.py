"""Decoder-only LM family covering the five assigned architectures.

One parameterized model: GQA or MLA attention, dense-SwiGLU or MoE FFN,
qk-norm / qkv-bias options, optional ROBE-compressed token embedding (the
paper's technique applied to the LM vocab table — see DESIGN.md §5).

Layers run under ``lax.scan`` with optional remat so the HLO stays one
layer big (critical for compile time and for the 61-layer / 384-expert cell).
``first_k_dense`` leading layers (kimi-k2) are unrolled before the scan.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from repro.core.robe import RobeSpec, init_memory, robe_lookup
from repro.dist import api as dist
from repro.nn.attention import (AttnConfig, attention_apply, attention_init,
                                init_cache as attn_init_cache)
from repro.nn.core import normal_init, rms_norm_apply, rms_norm_init
from repro.nn.moe import MoeConfig, moe_apply_dense, moe_apply_ep, moe_init, \
    moe_param_specs


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int                        # dense-FFN hidden (per-expert if MoE)
    vocab: int
    attn_kind: str = "gqa"           # "gqa" | "mla"
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 1e6
    q_chunk: int = 512
    # MLA dims
    q_lora_rank: int = 768
    kv_lora_rank: int = 256
    qk_nope_dim: int = 64
    qk_rope_dim: int = 32
    v_head_dim: int = 64
    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared: int = 0
    first_k_dense: int = 0
    d_ff_dense: int = 0              # hidden of the unrolled dense layers
    moe_dispatch: str = "dense"
    capacity_factor: float = 1.25
    # embedding compression (the paper's technique)
    embedding: str = "full"          # "full" | "robe"
    robe_size: int = 0
    robe_block: int = 32
    # numerics / memory
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32     # bf16 for the 1T cell (FSDP + bf16)
    remat: bool = True
    cache_dtype: Any = jnp.bfloat16

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def vocab_padded(self) -> int:
        """Vocab rounded up so embed/lm_head shard on any mesh ≤ 512; the
        CE loss masks the padded logits to -inf (see loss_fn)."""
        if self.vocab < 4096:
            return self.vocab          # smoke configs: keep exact
        return ((self.vocab + 511) // 512) * 512

    def attn_cfg(self) -> AttnConfig:
        return AttnConfig(
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
            kind=self.attn_kind, qk_norm=self.qk_norm,
            qkv_bias=self.qkv_bias, rope_theta=self.rope_theta,
            q_chunk=self.q_chunk, q_lora_rank=self.q_lora_rank,
            kv_lora_rank=self.kv_lora_rank, qk_nope_dim=self.qk_nope_dim,
            qk_rope_dim=self.qk_rope_dim, v_head_dim=self.v_head_dim)

    def moe_cfg(self) -> MoeConfig:
        return MoeConfig(d_model=self.d_model, d_ff=self.d_ff,
                         n_experts=self.n_experts, top_k=self.top_k,
                         n_shared=self.n_shared,
                         capacity_factor=self.capacity_factor,
                         dispatch=self.moe_dispatch)

    def robe_spec(self) -> RobeSpec:
        return RobeSpec(size=self.robe_size, block_size=self.robe_block,
                        seed=17)

    def param_count(self) -> int:
        """Total parameters (for 6·N·D model-flops accounting)."""
        d, f = self.d_model, self.d_ff
        if self.attn_kind == "mla":
            qd = self.qk_nope_dim + self.qk_rope_dim
            attn = (d * self.q_lora_rank + self.q_lora_rank * self.n_heads * qd
                    + d * (self.kv_lora_rank + self.qk_rope_dim)
                    + self.kv_lora_rank * self.n_heads
                    * (self.qk_nope_dim + self.v_head_dim)
                    + self.n_heads * self.v_head_dim * d)
        else:
            attn = d * self.head_dim * (self.n_heads * 2
                                        + self.n_kv_heads * 2)
        if self.is_moe:
            ffn = 3 * d * f * self.n_experts + d * self.n_experts \
                + 3 * d * f * self.n_shared
            dense_layers = self.first_k_dense
            moe_layers = self.n_layers - dense_layers
            per = attn * self.n_layers + ffn * moe_layers \
                + 3 * d * self.d_ff_dense * dense_layers
        else:
            per = (attn + 3 * d * f) * self.n_layers
        return per + 2 * self.vocab * d   # embed + head

    def active_param_count(self) -> int:
        """Activated params per token (MoE: top-k + shared only)."""
        if not self.is_moe:
            return self.param_count()
        d, f = self.d_model, self.d_ff
        attn = self.param_count() - (3 * d * f * self.n_experts
                                     + d * self.n_experts) \
            * (self.n_layers - self.first_k_dense) - 2 * self.vocab * d
        # attn now holds everything except routed experts and embeddings
        act_ffn = 3 * d * f * self.top_k * (self.n_layers
                                            - self.first_k_dense)
        return attn + act_ffn + 2 * self.vocab * d


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _dense_ffn_init(key, d: int, f: int) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w_gate": normal_init(k1, (d, f), 0.02),
            "w_up": normal_init(k2, (d, f), 0.02),
            "w_down": normal_init(k3, (f, d), 0.02)}


def _dense_ffn_apply(p, x):
    h = jax.nn.silu(x @ p["w_gate"].astype(x.dtype)) \
        * (x @ p["w_up"].astype(x.dtype))
    h = dist.shard(h, "batch", None, "mlp")
    return h @ p["w_down"].astype(x.dtype)


def _layer_init(key, cfg: TransformerConfig, moe: bool) -> dict:
    k1, k2 = jax.random.split(key)
    p = {"attn_norm": rms_norm_init(cfg.d_model),
         "ffn_norm": rms_norm_init(cfg.d_model),
         "attn": attention_init(k1, cfg.attn_cfg())}
    if moe:
        p["moe"] = moe_init(k2, cfg.moe_cfg())
    else:
        f = cfg.d_ff_dense if (cfg.is_moe and cfg.d_ff_dense) else cfg.d_ff
        p["ffn"] = _dense_ffn_init(k2, cfg.d_model, f)
    return p


def init_params(key, cfg: TransformerConfig) -> dict:
    ke, kl, kh = jax.random.split(key, 3)
    params: dict = {}
    if cfg.embedding == "robe":
        params["embed"] = {"memory": init_memory(ke, cfg.robe_spec())}
    else:
        params["embed"] = {"table": normal_init(
            ke, (cfg.vocab_padded, cfg.d_model), 0.02)}
    keys = jax.random.split(kl, cfg.n_layers)
    if cfg.first_k_dense:
        params["dense_layers"] = [
            _layer_init(keys[i], cfg, moe=False)
            for i in range(cfg.first_k_dense)]
    params["layers"] = jax.vmap(
        lambda k: _layer_init(k, cfg, moe=cfg.is_moe)
    )(jnp.stack(keys[cfg.first_k_dense:]))
    params["final_norm"] = rms_norm_init(cfg.d_model)
    params["lm_head"] = normal_init(kh, (cfg.d_model, cfg.vocab_padded),
                                    0.02)
    if cfg.param_dtype != jnp.float32:
        params = jax.tree.map(lambda x: x.astype(cfg.param_dtype), params)
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _embed(params, cfg: TransformerConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    if cfg.embedding == "robe":
        x = robe_lookup(params["embed"]["memory"], cfg.robe_spec(), 0,
                        tokens, cfg.d_model)
        return x.astype(cfg.compute_dtype)
    ctx = dist.current()
    table = params["embed"]["table"]
    v = table.shape[0]
    if ctx is not None:
        n_model = ctx.mesh.shape["model"]
        b, t = tokens.shape
        n_data = 1
        for a in ctx.dp_axes:
            n_data *= ctx.mesh.shape[a]
        if v % n_model == 0 and b % n_data == 0:
            # §Perf iteration (qwen3-0.6b hillclimb): explicit masked lookup
            # on the vocab-sharded table. Left to itself GSPMD all-gathers
            # the full fp32 table (622 MB/step for the qwen vocab); this
            # body moves one bf16 activation-sized reduce instead.
            from jax.sharding import PartitionSpec as P
            dp = ctx.rules.get("batch")
            rows = v // n_model
            scatter_ok = t % n_model == 0

            def body(tb, tok):
                m_idx = jax.lax.axis_index("model")
                local = tok - m_idx * rows
                hit = (local >= 0) & (local < rows)
                part = jnp.take(tb.astype(cfg.compute_dtype),
                                jnp.clip(local, 0, rows - 1), axis=0)
                part = jnp.where(hit[..., None], part, 0)
                if scatter_ok:   # deliver straight into the SP layout
                    return jax.lax.psum_scatter(part, "model",
                                                scatter_dimension=1,
                                                tiled=True)
                return jax.lax.psum(part, "model")

            out_spec = P(dp, "model", None) if scatter_ok \
                else P(dp, None, None)
            return jax.shard_map(
                body, mesh=ctx.mesh,
                in_specs=(P("model", None), P(dp, None)),
                out_specs=out_spec)(table, tokens)
    x = jnp.take(table, tokens, axis=0)
    return x.astype(cfg.compute_dtype)


def _moe_block(p, cfg: TransformerConfig, x: jnp.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [B,T,d] -> (y, aux)."""
    b, t, d = x.shape
    mcfg = cfg.moe_cfg()
    ctx = dist.current()
    if mcfg.dispatch == "ep" and ctx is not None:
        from jax.sharding import PartitionSpec as P
        rules = ctx.rules
        dp = rules.get("batch")
        specs = moe_param_specs(mcfg, rules)
        n_model = ctx.mesh.shape["model"]
        dp_t = (dp,) if isinstance(dp, str) else tuple(dp)
        # tokens shard over (dp, model) when T divides; decode (T=1) keeps
        # tokens on dp only — the EP all_to_all still spans the model axis.
        # aux pmean's only over axes the router output VARIES on (VMA rule).
        if t % n_model == 0:
            xs = P(dp, "model", None)
            aux_axes = dp_t + ("model",)
        else:
            xs = P(dp, None, None)
            aux_axes = dp_t

        def body(pp, xx):
            n_loc = xx.shape[0] * xx.shape[1]
            y, aux = moe_apply_ep(pp, mcfg, xx.reshape(n_loc, d),
                                  model_axis="model", aux_axes=aux_axes)
            return y.reshape(xx.shape), aux

        # decode (tokens replicated over model): every column dispatches the
        # same tokens and reassembles the full combine after the return
        # all_to_all — the output is semantically replicated over model but
        # VMA cannot infer it through all_to_all, hence check_vma=False.
        y, aux = jax.shard_map(
            body, mesh=ctx.mesh, in_specs=(specs, xs),
            out_specs=(xs, P()),
            check_vma=(t % n_model == 0))(p, x)
        return y, aux
    y, aux = moe_apply_dense(p, mcfg, x.reshape(b * t, d))
    return y.reshape(b, t, d), aux


def _layer_apply(p, cfg: TransformerConfig, moe: bool, x, positions,
                 collect_kv: bool = False):
    # Megatron-SP layout: x lives sequence-sharded between blocks.  NOTE
    # (§Perf iteration 3, REFUTED): forcing an explicit single all-gather of
    # each block's input made wire WORSE (+20%/layer) — GSPMD's own
    # placement (mixed all-to-all transposes) beats the hand-forced AG.
    h, kv = attention_apply(p["attn"], cfg.attn_cfg(),
                            rms_norm_apply(p["attn_norm"], x), positions,
                            return_kv=collect_kv)
    x = x + h
    x = dist.shard_if_divisible(x, ("batch", "seq", "embed"))
    hin = rms_norm_apply(p["ffn_norm"], x)
    if moe:
        h, aux = _moe_block(p["moe"], cfg, hin)
    else:
        h, aux = _dense_ffn_apply(p["ffn"], hin), jnp.zeros((), jnp.float32)
    x = x + h
    x = dist.shard_if_divisible(x, ("batch", "seq", "embed"))
    return x, aux, kv


def _shard_kv(cfg, kv):
    if kv is None:
        return None
    # prefill caches: batch over dp, sequence over model (divisible for any
    # head count — see DESIGN.md; decode reads it back the same way)
    return {k: dist.shard(v, "batch", "seq_kv_model", *((None,) *
                                                        (v.ndim - 2)))
            for k, v in kv.items()}


def forward(params, cfg: TransformerConfig, tokens: jnp.ndarray,
            collect_cache: bool = False, logits_mode: str = "all"):
    """tokens [B,T] -> (logits, aux[, cache]).

    logits_mode: "all" (training) | "last" (prefill serving — avoids the
    [B,T,V] logits tensor)."""
    x = _embed(params, cfg, tokens)
    x = dist.shard_if_divisible(x, ("batch", "seq", "embed"))
    positions = jnp.arange(tokens.shape[1])
    aux_total = jnp.zeros((), jnp.float32)
    dense_kv = []
    for p in params.get("dense_layers", []):
        x, aux, kv = _layer_apply(p, cfg, False, x, positions, collect_cache)
        aux_total += aux
        dense_kv.append(_shard_kv(cfg, kv))

    def scan_body(carry, layer_p):
        xx, aux_acc = carry
        xx, aux, kv = _layer_apply(layer_p, cfg, cfg.is_moe, xx, positions,
                                   collect_cache)
        return (xx, aux_acc + aux), _shard_kv(cfg, kv)

    body = jax.checkpoint(scan_body) if cfg.remat else scan_body
    (x, aux_total), kv_stack = jax.lax.scan(body, (x, aux_total),
                                            params["layers"])
    x = rms_norm_apply(params["final_norm"], x)
    if logits_mode == "last":
        x = x[:, -1]
        logits = x @ params["lm_head"].astype(x.dtype)
        logits = dist.shard(logits, "batch", "vocab")
    else:
        logits = x @ params["lm_head"].astype(x.dtype)
        logits = dist.shard(logits, "batch", None, "vocab")
    if collect_cache:
        cache = {"layers": kv_stack}
        if dense_kv:
            cache["dense_layers"] = dense_kv
        return logits, aux_total, cache
    return logits, aux_total


def loss_fn(params, cfg: TransformerConfig, batch: dict
            ) -> Tuple[jnp.ndarray, dict]:
    logits, aux = forward(params, cfg, batch["tokens"])
    labels = batch["labels"]
    # vocab-parallel CE: every reduction is over the (model-sharded) vocab
    # axis and elementwise otherwise — no gather along the sharded dim
    # (take_along_axis there would force GSPMD to replicate the logits).
    # §Perf: logits STAY in compute dtype so the TP boundary collectives of
    # the backward (d-logits partial-sum ARs) move bf16, not f32; only the
    # max-shifted exp/sum runs in f32.
    lg = logits
    iota = jax.lax.broadcasted_iota(jnp.int32, lg.shape, lg.ndim - 1)
    if cfg.vocab_padded != cfg.vocab:
        lg = jnp.where(iota < cfg.vocab, lg, jnp.asarray(-1e30, lg.dtype))
    m = jax.lax.stop_gradient(lg.max(axis=-1, keepdims=True)
                              ).astype(jnp.float32)
    ex = jnp.exp(lg.astype(jnp.float32) - m)
    lse = jnp.log(jnp.sum(ex, axis=-1)) + m[..., 0]
    gold = jnp.sum(jnp.where(iota == labels[..., None], lg, 0
                             ).astype(jnp.float32), axis=-1)
    ce = (lse - gold).mean()
    loss = ce + 0.001 * aux
    return loss, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------

def init_cache(cfg: TransformerConfig, batch: int, max_len: int):
    one = lambda: attn_init_cache(cfg.attn_cfg(), batch, max_len,
                                  cfg.cache_dtype)
    caches = {"layers": jax.tree.map(
        lambda x: jnp.broadcast_to(
            x, (cfg.n_layers - cfg.first_k_dense,) + x.shape),
        one())}
    if cfg.first_k_dense:
        caches["dense_layers"] = [one() for _ in range(cfg.first_k_dense)]
    return caches


def _layer_decode(p, cfg: TransformerConfig, moe: bool, x, cache, pos,
                  kv_len):
    positions = jnp.full((x.shape[1],), pos, jnp.int32)
    h, cache = attention_apply(p["attn"], cfg.attn_cfg(),
                               rms_norm_apply(p["attn_norm"], x), positions,
                               cache=cache, kv_len=kv_len)
    x = x + h
    hin = rms_norm_apply(p["ffn_norm"], x)
    if moe:
        h, _ = _moe_block(p["moe"], cfg, hin)
    else:
        h = _dense_ffn_apply(p["ffn"], hin)
    return x + h, cache


def decode_step(params, cfg: TransformerConfig, caches, tokens: jnp.ndarray,
                pos) -> Tuple[jnp.ndarray, Any]:
    """One decode step: tokens [B,1] at position ``pos`` with a filled KV
    cache of length pos. Returns (logits [B,V], updated caches)."""
    b = tokens.shape[0]
    kv_len = jnp.full((b,), pos + 1, jnp.int32)
    x = _embed(params, cfg, tokens)
    x = dist.shard(x, "batch", None, "embed")
    new_dense = []
    for p, c in zip(params.get("dense_layers", []),
                    caches.get("dense_layers", [])):
        x, c = _layer_decode(p, cfg, False, x, c, pos, kv_len)
        new_dense.append(c)

    def scan_body(xx, args):
        layer_p, layer_c = args
        xx, layer_c = _layer_decode(layer_p, cfg, cfg.is_moe, xx, layer_c,
                                    pos, kv_len)
        return xx, layer_c

    x, new_cache = jax.lax.scan(scan_body, x,
                                (params["layers"], caches["layers"]))
    x = rms_norm_apply(params["final_norm"], x)
    logits = x[:, -1] @ params["lm_head"].astype(x.dtype)
    logits = dist.shard(logits, "batch", "vocab")
    out = {"layers": new_cache}
    if new_dense:
        out["dense_layers"] = new_dense
    return logits, out
