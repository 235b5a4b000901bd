"""Plain float32 reference of a dense GQA decoder, and the weights the
benchmark serves it with.

Follows what the program serves, written from the equations and importing
nothing of the program:

    x_0   = E[tokens]
    h     = rms(x) * (1 + g)                  g: a norm's weight
    attn  = softmax(rope(h Wq) rope(h Wk)^T / sqrt(hd) + causal) (h Wv) Wo
            with n_heads / n_kv_heads query heads sharing each K/V head
    x    += attn(rms1(x));  x += (silu(h Wg) * (h Wu)) Wd,  h = rms2(x)
    logits = rms_f(x) (E^T if tied, else W_head)

RoPE rotates the two halves of each head (x1, x2) by pos * theta^(-2i/hd).
Every matmul runs at `Precision.HIGHEST`, so the TPU does not drop to one
bfloat16 pass. Attention is computed in blocks of query rows, so a long
sequence fits beside the weights.

`CONTROL` is the same forward with every linear layer's inputs rounded to
float8 (e4m3, one scale per weight matrix and per activation row): the
precision below the bfloat16 the configurations state, which the
comparison has to reject.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block
T_BUCKET = 1024        # sequences are padded up to a multiple of this
ROW_BLOCK = 256        # logit rows reduced at a time
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0

EXACT = "exact"
CONTROL = "control"


def dims(c: dict) -> Dict[str, int]:
    """Sizes of a configuration file (Hugging Face key names)."""
    return dict(d=c["hidden_size"], H=c["num_attention_heads"],
                KV=c["num_key_value_heads"], hd=c["head_dim"],
                f=c["intermediate_size"], V=c["vocab_size"],
                L=c["num_hidden_layers"])


def layout(c: dict) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """Every weight as `path -> (shape, std)`; the program's parameter tree
    has to hold exactly these paths and shapes."""
    z = dims(c)
    d, H, KV, hd, f, V, L = (z[k] for k in ("d", "H", "KV", "hd", "f",
                                            "V", "L"))
    out = {
        "embed": ((V, d), 0.02),
        "ln_f": ((d,), 0.1),
        "layers/ln1": ((L, d), 0.1),
        "layers/ln2": ((L, d), 0.1),
        "layers/attn/wq": ((L, d, H * hd), 1 / math.sqrt(d)),
        "layers/attn/wk": ((L, d, KV * hd), 1 / math.sqrt(d)),
        "layers/attn/wv": ((L, d, KV * hd), 1 / math.sqrt(d)),
        "layers/attn/wo": ((L, H * hd, d), 0.5 / math.sqrt(H * hd)),
        "layers/mlp/w_gate": ((L, d, f), 1 / math.sqrt(d)),
        "layers/mlp/w_up": ((L, d, f), 1 / math.sqrt(d)),
        "layers/mlp/w_down": ((L, f, d), 0.5 / math.sqrt(f)),
    }
    if not c["tie_word_embeddings"]:
        out["lm_head"] = ((d, V), 1 / math.sqrt(d))
    return out


def make_weights(c: dict, key, dtype) -> Dict[str, jax.Array]:
    """All weights, drawn from `key` on the device in one jitted call, in
    the type they are served in. Returns a flat `path -> array` dict."""
    lay = layout(c)
    names = sorted(lay)

    def draw(key):
        keys = jax.random.split(key, len(names))
        return {n: (jax.random.normal(k, lay[n][0], jnp.float32)
                    * lay[n][1]).astype(dtype)
                for n, k in zip(names, keys)}

    # the key is an argument, not a constant: one program for every seed
    return jax.jit(draw)(key)


# -- forward ---------------------------------------------------------------------


def _mm(x, w, mode):
    if mode == CONTROL:
        x, w = _f8_rows(x), _f8_tensor(w)
    return jnp.matmul(x, w, precision=HIGHEST)


def _f8_tensor(w):
    s = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30) / F8_MAX
    return (w / s).astype(F8).astype(jnp.float32) * s


def _f8_rows(x):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                    1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g)


def _rope(x, pos, theta):
    """x: (T, n, hd); pos: (T,)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv            # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, H, KV):
    """Causal GQA over the whole sequence, in blocks of query rows.
    q: (T, H, hd); k, v: (T, KV, hd)."""
    T, _, hd = q.shape
    G = H // KV
    nb = T // Q_BLOCK
    qb = q.reshape(nb, Q_BLOCK, KV, G, hd)
    kpos = jnp.arange(T)

    def block(args):
        i, qi = args
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k,
                       precision=HIGHEST) / math.sqrt(hd)
        s = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (jnp.arange(nb), qb))
    return out.reshape(T, H * hd)


@functools.partial(jax.jit, static_argnames=("shape", "mode"))
def _hidden(w, tokens, shape, mode):
    """Final hidden states (before the last norm) of one sequence."""
    H, KV, hd, L, theta, eps = shape
    T = tokens.shape[0]
    pos = jnp.arange(T)
    x = w["embed"][tokens].astype(jnp.float32)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731

    def layer(i, x):
        lw = {k[len("layers/"):]: f32(jax.lax.dynamic_index_in_dim(
            v, i, 0, keepdims=False))
            for k, v in w.items() if k.startswith("layers/")}
        h = _rms(x, lw["ln1"], eps)
        q = _rope(_mm(h, lw["attn/wq"], mode).reshape(T, H, hd), pos, theta)
        k = _rope(_mm(h, lw["attn/wk"], mode).reshape(T, KV, hd), pos, theta)
        v = _mm(h, lw["attn/wv"], mode).reshape(T, KV, hd)
        x = x + _mm(_attention(q, k, v, H, KV), lw["attn/wo"], mode)
        h = _rms(x, lw["ln2"], eps)
        g = jax.nn.silu(_mm(h, lw["mlp/w_gate"], mode)) \
            * _mm(h, lw["mlp/w_up"], mode)
        return x + _mm(g, lw["mlp/w_down"], mode)

    return jax.lax.fori_loop(0, L, layer, x)


@functools.partial(jax.jit, static_argnames=("eps", "tied"))
def _gaps(w, x_ref, x_ctl, served, eps, tied):
    """Per row: the reference's best logit minus its logit of the served
    token, and of the token the control puts first."""
    head = w["embed"].T if tied else w["lm_head"]
    head = head.astype(jnp.float32)
    g = w["ln_f"].astype(jnp.float32)

    def block(args):
        xr, xc, s = args
        lr = jnp.matmul(_rms(xr, g, eps), head, precision=HIGHEST)
        best = lr.max(-1)
        at_served = jnp.take_along_axis(lr, s[:, None], -1)[:, 0]
        lc = _mm(_rms(xc, g, eps), head, CONTROL)
        at_ctl = jnp.take_along_axis(lr, lc.argmax(-1)[:, None], -1)[:, 0]
        return best - at_served, best - at_ctl

    n = x_ref.shape[0] // ROW_BLOCK
    rs = lambda a: a.reshape((n, ROW_BLOCK) + a.shape[1:])  # noqa: E731
    gs, gc = jax.lax.map(block, (rs(x_ref), rs(x_ctl), rs(served)))
    return gs.reshape(-1), gc.reshape(-1)


def _pad(n: int, m: int) -> int:
    return -(-n // m) * m


def served_gaps(w: Dict[str, jax.Array], c: dict, prompt, served,
                control: bool = False) -> Tuple[np.ndarray, np.ndarray]:
    """Gaps of one served request: for each served token, by how much its
    reference logit lies below the reference's best at that position.
    With `control`, also the gaps of the tokens the float8 forward puts
    first (else that array is empty)."""
    z = dims(c)
    shape = (z["H"], z["KV"], z["hd"], z["L"], float(c["rope_theta"]),
             float(c["rms_norm_eps"]))
    seq = list(prompt) + list(served[:-1])
    T = len(seq)
    toks = np.zeros(_pad(T, T_BUCKET), np.int32)
    toks[:T] = seq
    # causal: the padding after the sequence changes no earlier row
    x = _hidden(w, jnp.asarray(toks), shape, EXACT)
    xc = _hidden(w, jnp.asarray(toks), shape, CONTROL) if control else x
    first = len(prompt) - 1
    n = len(served)
    rows = np.arange(first, first + n)
    npad = _pad(n, ROW_BLOCK)
    rows = np.concatenate([rows, np.full(npad - n, first)])
    sv = np.zeros(npad, np.int32)
    sv[:n] = served
    ridx = jnp.asarray(rows)
    gs, gc = _gaps(w, x[ridx], xc[ridx], jnp.asarray(sv),
                   float(c["rms_norm_eps"]), bool(c["tie_word_embeddings"]))
    gs, gc = np.asarray(gs)[:n], np.asarray(gc)[:n]
    return gs, (gc if control else np.zeros(0))


def full_logits(w: Dict[str, jax.Array], c: dict, tokens) -> np.ndarray:
    """All logits of one short sequence (for tests)."""
    z = dims(c)
    shape = (z["H"], z["KV"], z["hd"], z["L"], float(c["rope_theta"]),
             float(c["rms_norm_eps"]))
    T = len(tokens)
    toks = np.zeros(_pad(T, T_BUCKET), np.int32)
    toks[:T] = tokens
    x = _hidden(w, jnp.asarray(toks), shape, EXACT)[:T]
    head = w["embed"].T if c["tie_word_embeddings"] else w["lm_head"]
    h = _rms(x, w["ln_f"].astype(jnp.float32), float(c["rms_norm_eps"]))
    return np.asarray(jnp.matmul(h, head.astype(jnp.float32),
                                 precision=HIGHEST))
