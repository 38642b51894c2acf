"""RWKV-6 "Finch" (arXiv:2404.05892): attention-free LM with data-dependent
decay.  Assigned arch: rwkv6-1.6b (24L, d=2048, d_ff=7168, vocab=65536).

Time-mix block (per head, head dim 64):
    ddlerp token shift:  x_z = x + (x_prev - x) * (mu_z + lora_z(x_mix))
    r,k,v,g projections; decay  w_t = exp(-exp(w0 + lora_w(x_mix)))
    wkv recurrence:      y_t = (S_t + diag(u) k_t v_t^T)^T r_t
                         S_{t+1} = diag(w_t) S_t + k_t v_t^T
    GroupNorm per head, gate by silu(g), output projection.
Channel-mix block:  k = relu(W_k x_k)^2 ; out = sigmoid(W_r x_r) * (W_v k).

Training/prefill uses the CHUNKED-PARALLEL form of the recurrence (within a
chunk the interaction is an (C x C) decay-masked matmul -> MXU work; across
chunks a small state carry) — the TPU-native adaptation of the recurrence.
Decode carries (token-shift state, per-head S) — O(1) per token, which is
what makes the long_500k cell feasible for this arch.

The same chunked math is implemented as a Pallas kernel in
repro.kernels.rwkv6_scan; this module is the pure-jnp reference path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import (ParamSpec, constrain, dense_specs, dense,
                                 layer_norm, rms_norm, softmax_xent,
                                 stack_specs, abstract_params, init_params)
from repro.models.config import ModelConfig

LORA_RANK = 32


# ------------------------------------------------------------- wkv kernel
def wkv6_sequential(r, k, v, w, u, state):
    """Reference recurrence.  r,k,v,w: (T, dk|dv); u: (dk,);
    state: (dk, dv).  Returns (y (T, dv), final state)."""
    def step(S, inp):
        r_t, k_t, v_t, w_t = inp
        kv = k_t[:, None] * v_t[None, :]                 # (dk, dv)
        y = ((S + u[:, None] * kv) * r_t[:, None]).sum(0)
        S = w_t[:, None] * S + kv
        return S, y
    state, y = jax.lax.scan(step, state, (r, k, v, w))
    return y, state


def wkv6_chunked(r, k, v, w, u, state, *, chunk: int = 32):
    """Chunked-parallel form (exact same math, fp32 accumulators).

    Within a chunk: score(t,s) = sum_i r_t[i] k_s[i] * prod_{s<u<=t-1} w_u[i]
    expressed with per-channel cumulative log-decay; cross-chunk via the
    carried state.  All shapes (T, d); T % chunk == 0.
    """
    T, dk = r.shape
    dv = v.shape[1]
    C = chunk
    n = T // C
    rc = r.reshape(n, C, dk).astype(jnp.float32)
    kc = k.reshape(n, C, dk).astype(jnp.float32)
    vc = v.reshape(n, C, dv).astype(jnp.float32)
    wc = w.reshape(n, C, dk).astype(jnp.float32)
    uf = u.astype(jnp.float32)

    def chunk_step(S, inp):
        rr, kk, vv, ww = inp
        lw = jnp.log(jnp.maximum(ww, 1e-38))             # (C, dk) <= 0
        la = jnp.cumsum(lw, axis=0)                      # prod_{u<=t} w_u
        la_prev = la - lw                                # prod_{u<t}  w_u
        # within-chunk: decay from s+1..t-1 = exp(la_prev[t] - la[s])
        r_hat = rr * jnp.exp(la_prev)                    # (C, dk)
        k_hat = kk * jnp.exp(-la)                        # (C, dk)
        scores = r_hat @ k_hat.T                         # (C, C)
        mask = jnp.tril(jnp.ones((C, C), bool), k=-1)    # strict lower
        inner = jnp.where(mask, scores, 0.0) @ vv        # (C, dv)
        diag = ((rr * uf) * kk).sum(-1, keepdims=True) * vv
        cross = (rr * jnp.exp(la_prev)) @ S              # (C, dv)
        y = inner + diag + cross
        # state update: S' = diag(prod w) S + sum_s diag(prod_{s<u} w) k v^T
        decay_all = jnp.exp(la[-1])                      # (dk,)
        k_tail = kk * jnp.exp(la[-1][None, :] - la)      # (C, dk)
        S = decay_all[:, None] * S + k_tail.T @ vv
        return S, y

    state, y = jax.lax.scan(chunk_step, state.astype(jnp.float32),
                            (rc, kc, vc, wc))
    return y.reshape(T, dv).astype(r.dtype), state


# ------------------------------------------------------------------ specs
def _lora_spec(d: int, out: int, dt) -> dict:
    return {"a": ParamSpec((d, LORA_RANK), ("embed", None), dtype=dt),
            "b": ParamSpec((LORA_RANK, out), (None, "embed"), dtype=dt,
                           init="zeros")}


def _lora(p: dict, x: jax.Array) -> jax.Array:
    return jnp.tanh(x @ p["a"]) @ p["b"]


def time_mix_specs(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    dt = cfg.param_dtype
    s = {
        "mu_base": ParamSpec((d,), ("embed",), init="zeros", dtype=dt),
        "ln": ParamSpec((d,), ("embed",), init="ones", dtype=dt),
        "ln_b": ParamSpec((d,), ("embed",), init="zeros", dtype=dt),
        "gn": ParamSpec((d,), ("embed",), init="ones", dtype=dt),
        "gn_b": ParamSpec((d,), ("embed",), init="zeros", dtype=dt),
        "w0": ParamSpec((d,), ("embed",), init="zeros", dtype=dt),
        "u": ParamSpec((d,), ("embed",), init="zeros", dtype=dt),
        "o": dense_specs(d, d, ("heads", "embed"), dtype=dt),
    }
    for z in ("r", "k", "v", "g", "w"):
        s[f"mu_{z}"] = ParamSpec((d,), ("embed",), init="zeros", dtype=dt)
        s[f"lora_{z}"] = _lora_spec(d, d, dt)
    for z in ("r", "k", "v", "g"):
        s[z] = dense_specs(d, d, ("embed", "heads"), dtype=dt)
    return s


def channel_mix_specs(cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    dt = cfg.param_dtype
    return {
        "ln": ParamSpec((d,), ("embed",), init="ones", dtype=dt),
        "ln_b": ParamSpec((d,), ("embed",), init="zeros", dtype=dt),
        "mu_k": ParamSpec((d,), ("embed",), init="zeros", dtype=dt),
        "mu_r": ParamSpec((d,), ("embed",), init="zeros", dtype=dt),
        "k": dense_specs(d, f, ("embed", "mlp"), dtype=dt),
        "v": dense_specs(f, d, ("mlp", "embed"), dtype=dt),
        "r": dense_specs(d, d, ("embed", "heads"), dtype=dt),
    }


def _shift(x: jax.Array, prev: jax.Array) -> jax.Array:
    """Token shift: returns x_{t-1}; prev = last token of previous segment
    (B, D) (zeros at stream start)."""
    return jnp.concatenate([prev[:, None, :], x[:, :-1, :]], axis=1)


def _ddlerp(p, x, x_prev, z: str):
    dx = x_prev - x
    x_mix = x + dx * p["mu_base"]
    return x + dx * (p[f"mu_{z}"] + _lora(p[f"lora_{z}"], x_mix))


def time_mix(p, cfg: ModelConfig, x, prev_tok, wkv_state, *,
             use_kernel: bool = False):
    """x: (B,S,D); prev_tok: (B,D); wkv_state: (B,H,dk,dv) fp32."""
    B, S, D = x.shape
    dh = cfg.rwkv_head_dim
    H = D // dh
    xn = layer_norm(x, p["ln"], p["ln_b"])
    xp = _shift(xn, prev_tok)
    r = dense(p["r"], _ddlerp(p, xn, xp, "r"))
    k = dense(p["k"], _ddlerp(p, xn, xp, "k"))
    v = dense(p["v"], _ddlerp(p, xn, xp, "v"))
    g = jax.nn.silu(dense(p["g"], _ddlerp(p, xn, xp, "g")))
    w_log = p["w0"] + _lora(p["lora_w"], _ddlerp(p, xn, xp, "w"))
    w = jnp.exp(-jnp.exp(w_log.astype(jnp.float32))).astype(x.dtype)

    def split(t):
        return t.reshape(B, S, H, dh).transpose(0, 2, 1, 3)
    rh, kh, vh, wh = split(r), split(k), split(v), split(w)
    uh = p["u"].reshape(H, dh)

    if use_kernel:
        # Batched-heads Pallas dispatch: fold (B, H) into one grid axis so
        # the whole layer is a single pallas_call (prefill) or the fused
        # single-step kernel (decode) — no vmapped per-head launches.
        from repro.kernels import dispatch, rwkv6_scan
        BH = B * H
        fold = lambda t: t.reshape(BH, S, dh)
        uu = jnp.broadcast_to(uh[None], (B, H, dh)).reshape(BH, dh)
        ss = wkv_state.reshape(BH, dh, dh).astype(jnp.float32)
        if S == 1:
            yk, sk = rwkv6_scan.wkv6_decode(
                fold(rh)[:, 0], fold(kh)[:, 0], fold(vh)[:, 0],
                fold(wh)[:, 0], uu, ss)
            yk = yk[:, None, :]
        else:
            c = min(32, S)
            while S % c:
                c -= 1
            yk, sk = rwkv6_scan.wkv6_batched(
                fold(rh), fold(kh), fold(vh), fold(wh), uu, ss, chunk=c)
        y = yk.reshape(B, H, S, dh).astype(x.dtype)
        new_state = sk.reshape(B, H, dh, dh)
        dispatch.record("wkv6", "pallas")
    else:
        def per_head(r, k, v, w, u, s):
            if S == 1:
                return wkv6_sequential(r, k, v, w, u, s)
            c = 32 if S % 32 == 0 else 1
            if c == 1:
                return wkv6_sequential(r, k, v, w, u, s)
            return wkv6_chunked(r, k, v, w, u, s, chunk=c)

        y, new_state = jax.vmap(
            jax.vmap(per_head, in_axes=(0, 0, 0, 0, 0, 0)),
            in_axes=(0, 0, 0, 0, None, 0))(rh, kh, vh, wh, uh, wkv_state)
    y = y.transpose(0, 2, 1, 3).reshape(B, S, D)
    # per-head group norm
    yh = y.reshape(B, S, H, dh)
    yh = layer_norm(yh, None, None)
    y = yh.reshape(B, S, D) * p["gn"] + p["gn_b"]
    out = dense(p["o"], (y * g).astype(x.dtype))
    return out, xn[:, -1, :], new_state


def channel_mix(p, cfg: ModelConfig, x, prev_tok):
    xn = layer_norm(x, p["ln"], p["ln_b"])
    xp = _shift(xn, prev_tok)
    dx = xp - xn
    xk = xn + dx * p["mu_k"]
    xr = xn + dx * p["mu_r"]
    k = jnp.square(jax.nn.relu(dense(p["k"], xk)))
    return jax.nn.sigmoid(dense(p["r"], xr)) * dense(p["v"], k), xn[:, -1, :]


# ------------------------------------------------------------------ model
class RWKV6Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        assert cfg.d_model % cfg.rwkv_head_dim == 0
        self.n_heads_rwkv = cfg.d_model // cfg.rwkv_head_dim

    def param_specs(self):
        cfg = self.cfg
        dt = cfg.param_dtype
        layer = {"att": time_mix_specs(cfg), "ffn": channel_mix_specs(cfg)}
        return {
            "embed": ParamSpec((cfg.vocab_size, cfg.d_model),
                               ("vocab", "embed"), init="embed", dtype=dt),
            "ln_in": ParamSpec((cfg.d_model,), ("embed",), init="ones",
                               dtype=dt),
            "ln_in_b": ParamSpec((cfg.d_model,), ("embed",), init="zeros",
                                 dtype=dt),
            "layers": stack_specs(layer, cfg.n_layers),
            "ln_out": ParamSpec((cfg.d_model,), ("embed",), init="ones",
                                dtype=dt),
            "ln_out_b": ParamSpec((cfg.d_model,), ("embed",), init="zeros",
                                  dtype=dt),
            "head": ParamSpec((cfg.d_model, cfg.vocab_size),
                              ("embed", "vocab"), dtype=dt),
        }

    def init(self, key):
        return init_params(self.param_specs(), key)

    def abstract(self):
        return abstract_params(self.param_specs())

    # ---------------------------------------------------------- state
    def init_state(self, batch: int):
        cfg = self.cfg
        H, dh = self.n_heads_rwkv, cfg.rwkv_head_dim
        per_layer = {
            "att_tok": jnp.zeros((cfg.n_layers, batch, cfg.d_model),
                                 cfg.param_dtype),
            "ffn_tok": jnp.zeros((cfg.n_layers, batch, cfg.d_model),
                                 cfg.param_dtype),
            "wkv": jnp.zeros((cfg.n_layers, batch, H, dh, dh), jnp.float32),
        }
        return per_layer

    # -------------------------------------------------------- forward
    def forward(self, params, tokens, state=None, *, use_kernel=None,
                last_only=False):
        """tokens: (B, S) -> logits (B, S, V); carries state if given.
        use_kernel=None defers to cfg.use_kernel."""
        cfg = self.cfg
        if use_kernel is None:
            use_kernel = cfg.use_kernel
        B, S = tokens.shape
        if state is None:
            state = self.init_state(B)
        x = jnp.take(params["embed"], tokens, axis=0)
        x = constrain(x, ("batch", "seq", "embed"))
        x = layer_norm(x, params["ln_in"], params["ln_in_b"])

        def body(carry, xs):
            h = carry
            lp, att_tok, ffn_tok, wkv = xs
            y, att_tok, wkv = time_mix(lp["att"], cfg, h, att_tok, wkv,
                                       use_kernel=use_kernel)
            h = h + y
            y, ffn_tok = channel_mix(lp["ffn"], cfg, h, ffn_tok)
            h = h + y
            h = constrain(h, ("batch", "seq", "embed"))
            return h, (att_tok, ffn_tok, wkv)

        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable,
            prevent_cse=False)
        x, (att_tok, ffn_tok, wkv) = jax.lax.scan(
            body, x,
            (params["layers"], state["att_tok"], state["ffn_tok"],
             state["wkv"]))
        x = layer_norm(x, params["ln_out"], params["ln_out_b"])
        if last_only:
            x = x[:, -1:, :]
        logits = x @ params["head"]
        new_state = {"att_tok": att_tok, "ffn_tok": ffn_tok, "wkv": wkv}
        return logits, new_state

    def loss(self, params, batch):
        logits, _ = self.forward(params, batch["tokens"])
        return softmax_xent(logits, batch["labels"],
                            batch.get("mask")), {}

    def cache_axes(self):
        return {"att_tok": ("layers", "batch", "embed"),
                "ffn_tok": ("layers", "batch", "embed"),
                "wkv": ("layers", "batch", "heads", None, None)}

    # --------------------------------------------------------- decode
    def init_cache(self, batch: int, max_len: int):
        return self.init_state(batch)     # O(1) state; max_len unused

    def prefill(self, params, cache, tokens):
        """Prompt prefill: one stateful full-sequence pass — the carried
        (token-shift, wkv) state IS the decode cache, so prefill is just
        ``forward`` with ``last_only`` (chunked-parallel wkv when S
        divides into chunks; exact sequential twin otherwise).  Returns
        (last-position logits (B, 1, V), state)."""
        return self.forward(params, tokens, cache, last_only=True)

    def decode_step(self, params, cache, tokens, pos):
        """tokens: (B, 1). pos unused (stateful recurrence)."""
        logits, new_state = self.forward(params, tokens, cache)
        return logits[:, -1:], new_state
