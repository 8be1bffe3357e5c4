"""Unified transformer assembly for all six assigned families.

One `Model` covers dense / MoE / SSM / hybrid / VLM / audio by
composing the block modules according to `ModelConfig`:

    dense/vlm : x += attn(norm(x));            x += ffn(norm(x))
    moe       : x += attn(norm(x));            x += moe(norm(x)) [+dense]
    ssm       : x += ssd(norm(x))
    hybrid    : x += mean(attn(norm_a(x)), ssd(norm_s(x))); x += ffn(...)
    audio     : encoder-only dense (bidirectional, masked-prediction)

Parameters are stacked over layers and iterated with `lax.scan`
(HLO size independent of depth), with `jax.checkpoint` on the body
when remat is enabled. The OSDP plan decides per-operator shardings
through `sharding.specs` and per-operator splitting through
`Decision.split`.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.core.cost_model import DP, Decision
from repro.core.descriptions import loss_chunk
from repro.models import attention as attn_mod
from repro.models import ffn as ffn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import AttnGeom, attn_geometry, norm, positions_for
from repro.sharding.specs import (ParamSet, WeightSpec, build_param_set,
                                  seg_matmul)

LayerParams = Dict[str, jax.Array]


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------

def build_specs(cfg: ModelConfig, tp_size: int) -> List[WeightSpec]:
    d, L, Vp = cfg.d_model, cfg.n_layers, cfg.padded_vocab
    ln = cfg.norm == "layernorm"
    specs: List[WeightSpec] = []

    def w(path, shape, op, tp=None, zdp=None, init="normal", stacked=False,
          scale=0.02):
        specs.append(WeightSpec(path, shape, op, tp_axis=tp, zdp_axis=zdp,
                                init=init, stacked=stacked, init_scale=scale))

    # embeddings / head
    if cfg.family == "audio":
        w("embed/mask", (d,), "embed.tok")
    else:
        w("embed/tok", (Vp, d), "embed.tok", tp=0, zdp=1)
    if (not cfg.tie_embeddings and cfg.is_decoder) or cfg.encoder_only:
        w("head/out", (d, Vp), "head.out", tp=1, zdp=0)
    w("final_norm/scale", (d,), "final_norm", init="ones")
    if ln:
        w("final_norm/bias", (d,), "final_norm", init="zeros")

    geom = attn_geometry(cfg, tp_size) if cfg.has_attention else None
    if geom is not None:
        qf, kf = geom.q_flat, geom.kv_flat
        tp_q = 2 if geom.tp else None
        tp_b = 1 if geom.tp else None
        w("layers/attn/wq", (L, d, qf), "layers.attn_qkv", tp=tp_q, zdp=1,
          stacked=True, init="fan_in")
        w("layers/attn/wk", (L, d, kf), "layers.attn_qkv", zdp=1,
          stacked=True, init="fan_in")
        w("layers/attn/wv", (L, d, kf), "layers.attn_qkv", zdp=1,
          stacked=True, init="fan_in")
        if cfg.qkv_bias:
            w("layers/attn/bq", (L, qf), "layers.attn_qkv", tp=tp_b,
              init="zeros", stacked=True)
            w("layers/attn/bk", (L, kf), "layers.attn_qkv", init="zeros",
              stacked=True)
            w("layers/attn/bv", (L, kf), "layers.attn_qkv", init="zeros",
              stacked=True)
        w("layers/attn/wo", (L, qf, d), "layers.attn_out",
          tp=(1 if geom.tp else None), zdp=2, stacked=True, init="fan_in")
        w("layers/attn/norm_scale", (L, d), "layers.attn_norm", init="ones",
          stacked=True)
        if ln:
            w("layers/attn/norm_bias", (L, d), "layers.attn_norm",
              init="zeros", stacked=True)

    if cfg.has_ssm:
        di, ns, nh = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_n_heads
        w("layers/ssm/w_zx", (L, d, 2 * di), "layers.ssm_in", tp=2, zdp=1,
          stacked=True, init="fan_in")
        w("layers/ssm/w_bcdt", (L, d, 2 * ns + nh), "layers.ssm_in", zdp=1,
          stacked=True, init="fan_in")
        w("layers/ssm/wo", (L, di, d), "layers.ssm_out", tp=1, zdp=2,
          stacked=True, init="fan_in")
        w("layers/ssm/A_log", (L, nh), "layers.ssm_small", init="ssm_a",
          stacked=True)
        w("layers/ssm/D", (L, nh), "layers.ssm_small", init="ones",
          stacked=True)
        w("layers/ssm/dt_bias", (L, nh), "layers.ssm_small", init="zeros",
          stacked=True)
        w("layers/ssm/conv_w", (L, ssm_mod.CONV_K, di + 2 * ns),
          "layers.ssm_small", init="fan_in", stacked=True)
        w("layers/ssm/gate_norm", (L, di), "layers.ssm_small", init="ones",
          tp=1, stacked=True)
        w("layers/ssm/norm_scale", (L, d), "layers.ssm_norm", init="ones",
          stacked=True)

    ff_mult = 2 if cfg.act == "swiglu" else 1
    if cfg.is_moe:
        E, ff = cfg.moe_experts, cfg.d_ff
        w("layers/moe/router", (L, d, E), "layers.moe_router",
          stacked=True, init="fan_in")
        w("layers/moe/w13", (L, E, d, ff_mult * ff), "layers.moe_w13",
          tp=1, zdp=2, stacked=True, init="fan_in")
        w("layers/moe/w2", (L, E, ff, d), "layers.moe_w2", tp=1, zdp=2,
          stacked=True, init="fan_in")
        if cfg.moe_dense_residual:
            dff = cfg.moe_dense_d_ff or ff
            w("layers/moe/dense/w13", (L, d, ff_mult * dff),
              "layers.dense_w13", tp=2, zdp=1, stacked=True, init="fan_in")
            w("layers/moe/dense/w2", (L, dff, d), "layers.dense_w2", tp=1,
              zdp=2, stacked=True, init="fan_in")
        w("layers/moe/norm_scale", (L, d), "layers.ffn_norm", init="ones",
          stacked=True)
    elif cfg.d_ff:
        ff = cfg.d_ff
        w("layers/ffn/w13", (L, d, ff_mult * ff), "layers.ffn_w13", tp=2,
          zdp=1, stacked=True, init="fan_in")
        w("layers/ffn/w2", (L, ff, d), "layers.ffn_w2", tp=1, zdp=2,
          stacked=True, init="fan_in")
        w("layers/ffn/norm_scale", (L, d), "layers.ffn_norm", init="ones",
          stacked=True)
        if ln:
            w("layers/ffn/norm_bias", (L, d), "layers.ffn_norm",
              init="zeros", stacked=True)
    return specs


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------

@dataclass
class Model:
    cfg: ModelConfig
    geom: Optional[AttnGeom]
    pset: ParamSet
    decisions: Dict[str, Decision]
    # True = full per-layer jax.checkpoint, False = keep everything, or
    # a tuple of checkpoint_name tags to SAVE (selective per-slice
    # remat plans — everything un-named is rematerialized); see
    # models.registry._remat_policy / sharding.specs.seg_matmul tags
    remat: Union[bool, Tuple[str, ...]] = True
    swa_window: int = 0          # override window for long-context decode
    # residual-stream sharding (batch over data, d over model). Without
    # this GSPMD lets the ZDP embedding's d-over-data sharding evict the
    # batch sharding from the whole stack (§Perf iter 1: 16x activation
    # blow-up). None on single-device builds.
    residual_sharding: Optional[Any] = None

    @property
    def _mesh(self):
        return self.residual_sharding[0] if self.residual_sharding else None

    def _constrain(self, x: jax.Array) -> jax.Array:
        if self.residual_sharding is None:
            return x
        mesh, spec_fn = self.residual_sharding
        spec = spec_fn(x.shape)
        if spec is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(mesh, spec))

    # -- helpers ------------------------------------------------------------
    def _split_g(self, op: str) -> int:
        dec = self.decisions.get(op)
        if dec is None:
            return 1
        return dec.split if dec.uniform() is not None else 1

    def _layer_params(self, params: Dict[str, jax.Array]
                      ) -> Dict[str, jax.Array]:
        return {k: v for k, v in params.items() if k.startswith("layers/")}

    @jax.named_scope("norm")
    def _norm(self, lp, x, prefix):
        bias = lp.get(prefix + "_bias") if self.cfg.norm == "layernorm" \
            else None
        return norm(self.cfg, x, lp[prefix + "_scale"], bias)

    def _checkpoint(self, body):
        """Wrap a scan body per the plan's remat axis: full checkpoint,
        none, or a save-only-these-names selective policy."""
        if self.remat is True:
            return jax.checkpoint(body)
        if self.remat:   # tuple of checkpoint_name tags to save
            return jax.checkpoint(
                body, policy=jax.checkpoint_policies.save_only_these_names(
                    *self.remat))
        return body

    # -- embedding ----------------------------------------------------------
    @jax.named_scope("embed")
    def embed(self, params: Dict[str, jax.Array], batch: Dict[str, jax.Array]
              ) -> jax.Array:
        cfg = self.cfg
        if cfg.family == "audio":
            x = batch["frames"]
            if "mask" in batch:
                m = batch["mask"][..., None]
                x = jnp.where(m, params["embed/mask"].astype(x.dtype), x)
            return x
        tok = jnp.take(params["embed/tok"], batch["tokens"], axis=0)
        if cfg.family == "vlm" and "patches" in batch:
            x = jnp.concatenate(
                [batch["patches"].astype(tok.dtype), tok], axis=1)
        else:
            x = tok
        return x

    def logits(self, params: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
        cfg = self.cfg
        fb = params.get("final_norm/bias")
        with jax.named_scope("norm"):
            x = norm(cfg, x, params["final_norm/scale"], fb)
        if cfg.tie_embeddings:
            logits = x @ params["embed/tok"].T
        else:
            logits = seg_matmul(x, params, self.pset, "head/out", 0)
        # mask padded vocab entries
        if cfg.padded_vocab != cfg.vocab_size:
            pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
            logits = jnp.where(pad_mask, attn_mod.NEG_INF, logits)
        return logits

    # -- one layer ----------------------------------------------------------
    def _block(self, x: jax.Array, lp: LayerParams, positions: jax.Array,
               window: int) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        if cfg.family == "hybrid":
            h = self._norm(lp, x, "layers/attn/norm")
            a = attn_mod.attn_forward(cfg, self.geom, self.pset, lp, h,
                                      positions, window=window)
            hs = self._norm(lp, x, "layers/ssm/norm")
            s = ssm_mod.ssm_forward(cfg, self.pset, lp, hs)
            x = x + 0.5 * (a + s)
        elif cfg.has_attention:
            h = self._norm(lp, x, "layers/attn/norm")
            x = x + attn_mod.attn_forward(cfg, self.geom, self.pset, lp, h,
                                          positions, window=window)
        elif cfg.has_ssm:
            h = self._norm(lp, x, "layers/ssm/norm")
            x = x + ssm_mod.ssm_forward(cfg, self.pset, lp, h)
        if cfg.is_moe:
            h = self._norm(lp, x, "layers/moe/norm")
            y, aux = moe_mod.moe_forward(cfg, self.pset, lp, h, mesh=self._mesh)
            if cfg.moe_dense_residual:
                y = y + ffn_mod.ffn_forward(
                    cfg, self.pset, lp, h, prefix="layers/moe/dense",
                    granularity=self._split_g("layers.dense_w13"))
            x = x + y
        elif cfg.d_ff:
            h = self._norm(lp, x, "layers/ffn/norm")
            x = x + ffn_mod.ffn_forward(
                cfg, self.pset, lp, h,
                granularity=self._split_g("layers.ffn_w13"))
        return x, aux

    # -- full-sequence forward (train / prefill) -----------------------------
    def forward(self, params: Dict[str, jax.Array],
                batch: Dict[str, jax.Array], *,
                window: int = 0) -> Tuple[jax.Array, jax.Array]:
        """Returns (hidden_states (B,S,d), aux_loss)."""
        x = self.embed(params, batch)
        positions = positions_for(self.cfg, batch, x.shape[1])
        layer_params = self._layer_params(params)
        win = window or self.cfg.sliding_window

        x = self._constrain(x)

        def body(carry, lp):
            x, aux = carry
            x = self._constrain(x)
            x, a = self._block(x, lp, positions, win)
            x = self._constrain(x)
            return (x, aux + a), None

        body = self._checkpoint(body)
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   layer_params)
        return x, aux

    # -- losses ---------------------------------------------------------------
    def _ce_block(self, params, x_blk, lab_blk) -> Tuple[jax.Array,
                                                         jax.Array]:
        """Summed NLL + valid count for one (B, c, d) block."""
        logits = self.logits(params, x_blk).astype(jnp.float32)
        valid = lab_blk >= 0
        lab = jnp.where(valid, lab_blk, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
        return (jnp.where(valid, nll, 0.0).sum(),
                valid.sum().astype(jnp.float32))

    def loss_fn(self, params: Dict[str, jax.Array],
                batch: Dict[str, jax.Array]) -> Tuple[jax.Array, Dict]:
        cfg = self.cfg
        x, aux = self.forward(params, batch)
        if cfg.family == "vlm" and "patches" in batch:
            x = x[:, batch["patches"].shape[1]:]   # loss on text positions
        labels = batch["labels"]
        S = x.shape[1]
        # chunk the vocab projection over the sequence so the fp32
        # (B, S, V) logits never fully materialize (beyond-paper;
        # matters for the 128k-200k vocab archs at seq 4k)
        chunk = loss_chunk(S, cfg.padded_vocab)
        with jax.named_scope("loss"):
            if chunk < S:
                nb = S // chunk
                xb = jnp.moveaxis(
                    x.reshape(x.shape[0], nb, chunk, x.shape[-1]), 1, 0)
                lb = jnp.moveaxis(
                    labels.reshape(labels.shape[0], nb, chunk), 1, 0)

                def body(carry, blk):
                    s, n = carry
                    bs, bn = jax.checkpoint(self._ce_block)(params, *blk)
                    return (s + bs, n + bn), None

                (nll_sum, n_valid), _ = jax.lax.scan(
                    body, (jnp.zeros(()), jnp.zeros(())), (xb, lb))
            else:
                nll_sum, n_valid = self._ce_block(params, x, labels)
            denom = jnp.maximum(n_valid, 1.0)
            ce = nll_sum / denom
            loss = ce + 0.01 * aux / max(1, cfg.n_layers)
        return loss, {"ce": ce, "aux": aux, "tokens": n_valid}

    # -- serving --------------------------------------------------------------
    def init_caches(self, batch: int, cache_len: int) -> Dict[str, Any]:
        caches: Dict[str, Any] = {}
        cfg = self.cfg
        if cfg.has_attention:
            win = self.swa_window or cfg.sliding_window
            alen = min(cache_len, win) if win else cache_len
            caches["attn"] = attn_mod.init_kv_cache(cfg, self.geom, batch,
                                                    alen)
        if cfg.has_ssm:
            caches["ssm"] = ssm_mod.init_ssm_cache(cfg, batch)
        return caches

    def decode_step(self, params: Dict[str, jax.Array],
                    caches: Dict[str, Any], tokens: jax.Array, t: jax.Array,
                    positions3: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, Dict[str, Any]]:
        """One token for the whole batch. tokens: (B,1) int32; t is a
        scalar position or a (B,) vector (continuous batching decodes
        every slot at its own position)."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = jnp.take(params["embed/tok"], tokens, axis=0)
        layer_params = self._layer_params(params)
        win = self.swa_window or cfg.sliding_window

        xs: Dict[str, Any] = {"lp": layer_params}
        if "attn" in caches:
            xs["attn"] = caches["attn"]
        if "ssm" in caches:
            xs["ssm"] = caches["ssm"]

        def body(x, layer_in):
            lp = layer_in["lp"]
            new = {}
            if cfg.family == "hybrid":
                h = self._norm(lp, x, "layers/attn/norm")
                a, new_a = attn_mod.attn_decode(
                    cfg, self.geom, self.pset, lp, h, t, layer_in["attn"],
                    window=win, positions3=positions3)
                hs = self._norm(lp, x, "layers/ssm/norm")
                s, new_s = ssm_mod.ssm_decode(cfg, self.pset, lp, hs,
                                              layer_in["ssm"])
                x = x + 0.5 * (a + s)
                new["attn"], new["ssm"] = new_a, new_s
            elif cfg.has_attention:
                h = self._norm(lp, x, "layers/attn/norm")
                a, new_a = attn_mod.attn_decode(
                    cfg, self.geom, self.pset, lp, h, t, layer_in["attn"],
                    window=win, positions3=positions3)
                x = x + a
                new["attn"] = new_a
            elif cfg.has_ssm:
                h = self._norm(lp, x, "layers/ssm/norm")
                s, new_s = ssm_mod.ssm_decode(cfg, self.pset, lp, h,
                                              layer_in["ssm"])
                x = x + s
                new["ssm"] = new_s
            if cfg.is_moe:
                h = self._norm(lp, x, "layers/moe/norm")
                y, _ = moe_mod.moe_forward(cfg, self.pset, lp, h, mesh=self._mesh)
                if cfg.moe_dense_residual:
                    y = y + ffn_mod.ffn_forward(cfg, self.pset, lp, h,
                                                prefix="layers/moe/dense")
                x = x + y
            elif cfg.d_ff:
                h = self._norm(lp, x, "layers/ffn/norm")
                x = x + ffn_mod.ffn_forward(cfg, self.pset, lp, h)
            return x, new

        x, new_caches = jax.lax.scan(body, x, xs)
        logits = self.logits(params, x)
        return logits, new_caches

    def prefill(self, params: Dict[str, jax.Array],
                batch: Dict[str, jax.Array],
                cache_len: Optional[int] = None) -> Tuple[jax.Array, Dict]:
        """Full-sequence forward returning last-position logits + caches.

        Caches are rebuilt from a forward pass that also emits per-layer
        k/v (attention) and final states (ssm).  `cache_len` sizes the
        returned KV cache (>= S leaves free slots for decode — the
        continuous engine prefills straight into its slot shape);
        default S, the legacy rolling-cache behaviour."""
        cfg = self.cfg
        x = self.embed(params, batch)
        B, S = x.shape[:2]
        positions = positions_for(cfg, batch, S)
        win = self.swa_window or cfg.sliding_window
        target = cache_len or S
        alen = min(target, win) if win else target
        layer_params = self._layer_params(params)

        def body(carry, lp):
            x = self._constrain(carry)
            new = {}
            if cfg.family == "hybrid":
                h = self._norm(lp, x, "layers/attn/norm")
                a, kv = _attn_with_kv(self, lp, h, positions, win)
                hs = self._norm(lp, x, "layers/ssm/norm")
                s, st = _ssm_with_state(self, lp, hs)
                x = x + 0.5 * (a + s)
                new["attn"] = _kv_to_cache(kv, alen)
                new["ssm"] = st
            elif cfg.has_attention:
                h = self._norm(lp, x, "layers/attn/norm")
                a, kv = _attn_with_kv(self, lp, h, positions, win)
                x = x + a
                new["attn"] = _kv_to_cache(kv, alen)
            elif cfg.has_ssm:
                h = self._norm(lp, x, "layers/ssm/norm")
                s, st = _ssm_with_state(self, lp, h)
                x = x + s
                new["ssm"] = st
            if cfg.is_moe:
                h = self._norm(lp, x, "layers/moe/norm")
                y, _ = moe_mod.moe_forward(cfg, self.pset, lp, h, mesh=self._mesh)
                if cfg.moe_dense_residual:
                    y = y + ffn_mod.ffn_forward(cfg, self.pset, lp, h,
                                                prefix="layers/moe/dense")
                x = x + y
            elif cfg.d_ff:
                h = self._norm(lp, x, "layers/ffn/norm")
                x = x + ffn_mod.ffn_forward(cfg, self.pset, lp, h)
            return x, new

        body = self._checkpoint(body)
        x, caches = jax.lax.scan(body, x, layer_params)
        logits = self.logits(params, x[:, -1:])
        return logits, caches


def _attn_with_kv(model: Model, lp, h, positions, win):
    cfg, geom, pset = model.cfg, model.geom, model.pset
    from repro.models.common import rotate
    with jax.named_scope("attention/qkv"):
        q, k, v = attn_mod._proj_qkv(cfg, geom, pset, lp, h)
        q = rotate(cfg, q.reshape(*q.shape[:2], -1, geom.head_dim),
                   positions).reshape(q.shape)
        k = rotate(cfg, k, positions)
    with jax.named_scope("attention/core"):
        o = attn_mod.flash_attention(q, k, v, causal=cfg.causal, window=win)
    with jax.named_scope("attention/out"):
        return attn_mod._out_proj(geom, pset, lp, o), (k, v)


def _kv_to_cache(kv, alen: int):
    """The last min(S, alen) positions in ring-buffer order (position p
    at slot p % alen), built by padding or rolling: the TPU compiler
    aborts on the equivalent scatter inside the layer scan."""
    k, v = kv
    B, S = k.shape[:2]
    take = min(alen, S)
    pos = jnp.broadcast_to(jnp.arange(S - take, S, dtype=jnp.int32),
                           (B, take))

    def place(a, fill):
        a = a[:, S - take:]
        if take < alen:         # slots 0..S-1, the rest empty
            pad = [(0, 0)] * a.ndim
            pad[1] = (0, alen - take)
            return jnp.pad(a, pad, constant_values=fill)
        return jnp.roll(a, (S - alen) % alen, axis=1)

    return {"k": place(k, 0), "v": place(v, 0), "pos": place(pos, -1)}


@jax.named_scope("ssm")
def _ssm_with_state(model: Model, lp, h):
    cfg, pset = model.cfg, model.pset
    B, S, _ = h.shape
    di, ns, nh, hd = (cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_n_heads,
                      cfg.ssm_head_dim)
    z, xin, b, c, dt = ssm_mod._split_proj(cfg, pset, lp, h)
    conv_in = jnp.concatenate([xin, b, c], axis=-1)
    conv_out, conv_state = ssm_mod.causal_conv(conv_in, lp["layers/ssm/conv_w"])
    xin, b, c = (conv_out[..., :di], conv_out[..., di:di + ns],
                 conv_out[..., di + ns:])
    xh = xin.reshape(B, S, nh, hd)
    y, state = ssm_mod.ssd_chunk_scan(xh, dt, lp["layers/ssm/A_log"], b, c,
                                      cfg.ssm_chunk)
    y = y + xh.astype(jnp.float32) * lp["layers/ssm/D"].astype(
        jnp.float32)[None, None, :, None]
    y = y.reshape(B, S, di).astype(h.dtype)
    from repro.models.common import rmsnorm
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(h.dtype),
                lp["layers/ssm/gate_norm"])
    out = seg_matmul(y, lp, pset, "layers/ssm/wo", 0)
    # conv state of the last K-1 steps
    cache = {"state": state,
             "conv": conv_state.astype(jnp.float32)}
    return out, cache
