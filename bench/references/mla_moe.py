"""Plain float32 reference of a DeepSeek-V3-style MLA + held-expert MoE
decoder (Kimi-K2-Instruct's block), and the weights the benchmark serves
it with.

Written from the published equations, importing nothing of the program:

    x_0    = E[tokens]
    h      = rms(x) * (1 + g)                       g: a norm's weight
    MLA    q = rms(h Wqa) Wqb, per head [q_nope (nope) ; q_pe (rope)]
           [c ; k_pe] = h Wkva, c = rms(c) (kv_lora), k_pe one rotary key
           shared by every head; per head k = [c Wuk_h ; rope(k_pe)],
           v = c Wuv_h, q = [q_nope ; rope(q_pe)]
           attn = softmax(q k^T * s + causal) v, concatenated, times Wo
           s = (nope + rope)^-1/2 * yarn_mscale(factor, mscale_all_dim)^2
    layers 0 .. first_k_dense_replace-1: x += attn(rms1(x));
           x += (silu(h Wg) * (h Wu)) Wd, h = rms2(x)          (dense)
    other layers: the same attention, then the expert layer on h = rms2(x):
           scores = sigmoid(h Wr) over all router experts (fp32);
           the top-k of scores + bias are chosen (noaux_tc, one group);
           gate_e = scores_e / sum of the chosen * scale (norm_topk_prob);
           y = sum over the HELD experts e of gate_e * expert_e(h)
               (gate 0 where e was not chosen) + shared(h)
    logits = rms_f(x) W_head

The keys and values are those of the non-absorbed form: each head's K and
V come from the latent by its own up-projection, so the comparison also
tests the program's absorbed algebra (q_nope Wuk against c, then Wuv).

RoPE: yarn as DeepSeek-V3 (`yarn_find_correction_range`,
`yarn_linear_ramp_mask`, cos and sin times yarn_mscale(factor, mscale) /
yarn_mscale(factor, mscale_all_dim)), rotating the two halves (x1, x2) of
the rotary part. DeepSeek-V3 rotates interleaved pairs; the two are one
model with the rope columns of Wqb and Wkva permuted (pair i = columns
(2i, 2i+1) there, (i, i + rope/2) here), which is how real weights would
map.

The held share: the chip holds experts [first, first + held) of the
router's experts (`expert_share`); the router keeps its full width and
top-k, and what the absent experts would add is left out, here as in the
program.

Routing ties: top-k selection is discontinuous, and bfloat16 activations
move router scores by about 0.002-0.02; where a held expert sits that
close to the top-k boundary the program may choose the other side, and
the position's logits then differ by a whole expert's share (a bfloat16
program over 64 experts of which 8 are held read gaps up to 0.44 at CPU
widths, and 0.068 with 8 of 8 held, where no choice can flip). Such
positions are left out of the served gaps (`ROUTE_TIE`).

Every matmul runs at `Precision.HIGHEST`. Per-token work goes in blocks of
rows and attention in blocks of query rows and of heads, so a 16k-token
sequence fits beside the weights.

`CONTROL` is the same forward with every linear layer's inputs rounded to
float8 (e4m3, one scale per weight matrix and per activation row): the
precision below the bfloat16 the configuration states, which the
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
Q_BLOCK = 256          # query rows per attention block
HEAD_GROUP = 16        # heads per attention block
T_BUCKET = 1024        # sequences are padded up to a multiple of this
ROWS = 512             # token rows per block of the per-token work
ROW_BLOCK = 256        # logit rows reduced at a time
V_BLOCK = 8192         # vocabulary columns per block of the LM head
DRAW_CHUNK = 1 << 26   # weights larger than this are drawn in chunks
# A held expert whose score + bias lies this close to the top-k boundary
# (on the side it falls) may be chosen either way by a program whose
# activations are bfloat16: its router scores differ from these float32
# ones by 0.002-0.003 (median) and 0.008-0.018 (99th percentile) over the
# MoE layers of a 1024-wide stage of this block (CPU, 1024 tokens). One
# such flip moves the position's hidden state by a whole expert's share, so
# the position's logits say nothing of the program's arithmetic: positions
# with a tie in any layer are not compared (`served_gaps`).
ROUTE_TIE = 0.02
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0

EXACT = "exact"
CONTROL = "control"


def dims(c: dict) -> Dict[str, int]:
    """Sizes of a configuration file (Hugging Face key names; the
    router's width and the first held expert from `expert_share`)."""
    share = c["expert_share"]
    return dict(d=c["hidden_size"], H=c["num_attention_heads"],
                ql=c["q_lora_rank"], kl=c["kv_lora_rank"],
                nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
                vd=c["v_head_dim"], f=c["intermediate_size"],
                fe=c["moe_intermediate_size"],
                shared=c["n_shared_experts"] * c["moe_intermediate_size"],
                E=share["router_experts"], held=c["n_routed_experts"],
                first=share["first_held_expert"],
                K=c["num_experts_per_tok"], V=c["vocab_size"],
                L=c["num_hidden_layers"], Ld=c["first_k_dense_replace"])


def _attention_layout(n: int, z) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    d, H, ql, kl = z["d"], z["H"], z["ql"], z["kl"]
    qk = z["nope"] + z["rope"]
    return {
        "ln1": ((n, d), 0.1),
        "ln2": ((n, d), 0.1),
        "attn/wq_a": ((n, d, ql), 1 / math.sqrt(d)),
        "attn/q_norm": ((n, ql), 0.1),
        "attn/wq_b": ((n, ql, H * qk), 1 / math.sqrt(ql)),
        "attn/wkv_a": ((n, d, kl + z["rope"]), 1 / math.sqrt(d)),
        "attn/kv_norm": ((n, kl), 0.1),
        "attn/w_uk": ((n, kl, H * z["nope"]), 1 / math.sqrt(kl)),
        "attn/w_uv": ((n, kl, H * z["vd"]), 1 / math.sqrt(kl)),
        "attn/wo": ((n, H * z["vd"], d), 0.5 / math.sqrt(H * z["vd"])),
    }


def _mlp_layout(prefix: str, lead, d: int, f: int):
    return {
        f"{prefix}/w_gate": (lead + (d, f), 1 / math.sqrt(d)),
        f"{prefix}/w_up": (lead + (d, f), 1 / math.sqrt(d)),
        f"{prefix}/w_down": (lead + (f, d), 0.5 / math.sqrt(f)),
    }


def layout(c: dict) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """Every weight as `path -> (shape, std)`; the program's parameter tree
    has to hold exactly these paths and shapes."""
    z = dims(c)
    d, V, nd, nm = z["d"], z["V"], z["Ld"], z["L"] - z["Ld"]
    out = {"embed": ((V, d), 0.02), "ln_f": ((d,), 0.1),
           "lm_head": ((d, V), 1 / math.sqrt(d))}
    if nd:
        for k, v in _attention_layout(nd, z).items():
            out[f"dense_layers/{k}"] = v
        for k, v in _mlp_layout("mlp", (nd,), d, z["f"]).items():
            out[f"dense_layers/{k}"] = v
    moe = dict(_attention_layout(nm, z))
    moe["moe/router"] = ((nm, d, z["E"]), 1 / math.sqrt(d))
    moe["moe/router_bias"] = ((nm, z["E"]), 0.1)
    moe.update(_mlp_layout("moe", (nm, z["held"]), d, z["fe"]))
    moe.update(_mlp_layout("moe/shared", (nm,), d, z["shared"]))
    for k, v in moe.items():
        out[f"layers/{k}"] = v
    return out


def _draw(key, shape, std, dtype):
    """Normal(0, std) in `dtype`; a large weight in chunks along its first
    axis, so no float32 copy of it is ever whole."""
    n = int(np.prod(shape))
    parts = 1
    while n // parts > DRAW_CHUNK and shape[0] % (parts * 2) == 0:
        parts *= 2
    if parts == 1:
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)
    part = (shape[0] // parts,) + tuple(shape[1:])
    out = jax.lax.map(
        lambda k: (jax.random.normal(k, part, jnp.float32) * std
                   ).astype(dtype), jax.random.split(key, parts))
    return out.reshape(shape)


def make_weights(c: dict, key, dtype) -> Dict[str, jax.Array]:
    """All weights (the router's selection bias among them), drawn from
    `key` on the device in one jitted call, in the type they are served
    in. Returns a flat `path -> array` dict."""
    lay = layout(c)
    names = sorted(lay)

    def draw(key):
        keys = jax.random.split(key, len(names))
        return {n: _draw(k, lay[n][0], lay[n][1], dtype)
                for n, k in zip(names, keys)}

    # the key is an argument, not a constant: one program for every seed
    return jax.jit(draw)(key)


# -- forward ---------------------------------------------------------------------


def _f8_tensor(w, axes=None):
    """float8 rounding with one scale per matrix (over `axes`)."""
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=axes, keepdims=axes is not None),
                    1e-30) / F8_MAX
    return (w / s).astype(F8).astype(jnp.float32) * s


def _f8_rows(x):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                    1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(x, w, mode):
    if mode == CONTROL:
        x, w = _f8_rows(x), _f8_tensor(w)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g)


def _yarn_mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rope_tables(c: dict):
    """(inv_freq (rope/2,) float32, cos/sin scale) of the rotary part."""
    dim, theta = c["qk_rope_head_dim"], float(c["rope_theta"])
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    y = c.get("rope_scaling")
    if not y:
        return base.astype(np.float32), 1.0
    orig, f = y["original_max_position_embeddings"], y["factor"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    extra = 1.0 - ramp           # 1: the frequency kept, 0: divided by f
    inv = base / f * (1.0 - extra) + base * extra
    return inv.astype(np.float32), \
        _yarn_mscale(f, y["mscale"]) / _yarn_mscale(f, y["mscale_all_dim"])


def softmax_scale(c: dict) -> float:
    s = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5
    y = c.get("rope_scaling")
    if y and y.get("mscale_all_dim"):
        s *= _yarn_mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return s


def _rope(x, pos, inv, ms):
    """x: (T, n, rope); pos: (T,)."""
    r = x.shape[-1] // 2
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv)   # (T, r)
    cos = (jnp.cos(ang) * ms)[:, None, :]
    sin = (jnp.sin(ang) * ms)[:, None, :]
    x1, x2 = x[..., :r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _rows(fn, *arrays):
    """fn over blocks of ROWS leading rows (every array is (T, ...))."""
    T = arrays[0].shape[0]
    n = T // ROWS
    out = jax.lax.map(lambda a: fn(*a),
                      tuple(a.reshape((n, ROWS) + a.shape[1:])
                            for a in arrays))
    return jax.tree.map(lambda o: o.reshape((T,) + o.shape[2:]), out)


def _causal(q, k, v, scale):
    """Causal attention of one head group in blocks of query rows.
    q, k: (T, G, qk); v: (T, G, vd) -> (T, G, vd)."""
    T = q.shape[0]
    nb = T // Q_BLOCK
    kpos = jnp.arange(T)

    def block(args):
        i, qi = args
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qgd,tgd->gqt", qi, k, precision=HIGHEST) * scale
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("gqt,tgd->qgd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (jnp.arange(nb),
                              q.reshape((nb, Q_BLOCK) + q.shape[1:])))
    return out.reshape((T,) + out.shape[2:])


def _mla(lw, h, pos, z, cst, mode):
    """Non-absorbed MLA of one layer over the whole sequence (T, d)."""
    H, nope, rope, kl, vd = z["H"], z["nope"], z["rope"], z["kl"], z["vd"]
    eps, inv, ms, scale = cst
    T = h.shape[0]

    def proj(hb, pb):
        q = _mm(_rms(_mm(hb, lw["attn/wq_a"], mode), lw["attn/q_norm"], eps),
                lw["attn/wq_b"], mode).reshape(-1, H, nope + rope)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pb, inv,
                                                  ms)], -1)
        kv = _mm(hb, lw["attn/wkv_a"], mode)
        lat = _rms(kv[:, :kl], lw["attn/kv_norm"], eps)
        kpe = _rope(kv[:, None, kl:], pb, inv, ms)[:, 0]
        return q, lat, kpe

    q, lat, kpe = _rows(proj, h, pos)
    w_uk, w_uv = lw["attn/w_uk"], lw["attn/w_uv"]
    if mode == CONTROL:
        lat, w_uk, w_uv = _f8_rows(lat), _f8_tensor(w_uk), _f8_tensor(w_uv)
    w_uk = w_uk.reshape(kl, H, nope)
    w_uv = w_uv.reshape(kl, H, vd)
    G = min(HEAD_GROUP, H)

    def group(gi):
        sl = lambda a, ax: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, gi * G, G, axis=ax)
        k_nope = jnp.einsum("tc,cgn->tgn", lat, sl(w_uk, 1),
                            precision=HIGHEST)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kpe[:, None, :], (T, G, rope))], -1)
        v = jnp.einsum("tc,cgv->tgv", lat, sl(w_uv, 1), precision=HIGHEST)
        return _causal(sl(q, 1), k, v, scale)

    o = jax.lax.map(group, jnp.arange(H // G))           # (H/G, T, G, vd)
    o = jnp.moveaxis(o, 0, 1).reshape(T, H * vd)
    return _rows(lambda ob: _mm(ob, lw["attn/wo"], mode), o)


def _swiglu(x, wg, wu, wd, mode):
    return _mm(jax.nn.silu(_mm(x, wg, mode)) * _mm(x, wu, mode), wd, mode)


def _experts(lw, h, z, mode):
    """The held-expert layer's output for rows h (n, d), and which rows
    route a held expert within ROUTE_TIE of the top-k boundary."""
    s = jax.nn.sigmoid(_mm(h, lw["moe/router"], mode))       # (n, E)
    v = s + lw["moe/router_bias"]
    top, idx = jax.lax.top_k(v, z["K"] + 1)
    idx = idx[:, :z["K"]]
    vh = jax.lax.dynamic_slice_in_dim(v, z["first"], z["held"], axis=1)
    chosen = vh >= top[:, z["K"] - 1:z["K"]]
    tie = jnp.where(chosen, vh - top[:, z["K"]:], top[:, z["K"] - 1:z["K"]]
                    - vh) < ROUTE_TIE
    gate = jnp.take_along_axis(s, idx, axis=-1)
    gate = gate / (gate.sum(-1, keepdims=True) + 1e-20) * z["routed_scale"]
    hit = (idx - z["first"])[..., None] == jnp.arange(z["held"])
    g = jnp.sum(jnp.where(hit, gate[..., None], 0.0), axis=1)  # (n, held)
    wg, wu, wd = lw["moe/w_gate"], lw["moe/w_up"], lw["moe/w_down"]
    x = h
    if mode == CONTROL:
        x = _f8_rows(h)
        wg, wu, wd = (_f8_tensor(w, (1, 2)) for w in (wg, wu, wd))
    a = jnp.einsum("nd,edf->nef", x, wg, precision=HIGHEST)
    b = jnp.einsum("nd,edf->nef", x, wu, precision=HIGHEST)
    u = jax.nn.silu(a) * b
    if mode == CONTROL:
        u = _f8_rows(u)
    y = jnp.einsum("nef,efd,ne->nd", u, wd, g, precision=HIGHEST)
    return y + _swiglu(h, lw["moe/shared/w_gate"], lw["moe/shared/w_up"],
                       lw["moe/shared/w_down"], mode), tie.any(-1)


@functools.partial(jax.jit, static_argnames=("shape", "mode"))
def _hidden(w, tokens, shape, mode):
    """Final hidden states (before the last norm) of one sequence, and
    which of its positions route a held expert within ROUTE_TIE of the
    top-k boundary in some layer."""
    z, cst = dict(shape[0]), shape[1]
    eps = cst[0]
    T = tokens.shape[0]
    pos = jnp.arange(T)
    x = w["embed"][tokens].astype(jnp.float32)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731

    def stack(prefix, ffn):
        def layer(i, carry):
            x, tie = carry
            lw = {k[len(prefix):]: f32(jax.lax.dynamic_index_in_dim(
                v, i, 0, keepdims=False))
                for k, v in w.items() if k.startswith(prefix)}
            x = x + _mla(lw, _rms(x, lw["ln1"], eps), pos, z, cst, mode)
            y, t = _rows(lambda hb: ffn(lw, hb), _rms(x, lw["ln2"], eps))
            return x + y, tie | t
        return layer

    def dense(lw, h):
        return (_swiglu(h, lw["mlp/w_gate"], lw["mlp/w_up"], lw["mlp/w_down"],
                        mode), jnp.zeros(h.shape[:1], bool))

    moe = lambda lw, h: _experts(lw, h, z, mode)  # noqa: E731
    carry = (x, jnp.zeros((T,), bool))
    if z["Ld"]:
        carry = jax.lax.fori_loop(0, z["Ld"], stack("dense_layers/", dense),
                                  carry)
    return jax.lax.fori_loop(0, z["L"] - z["Ld"], stack("layers/", moe),
                             carry)


def _routing(c: dict) -> Dict:
    z = dims(c)
    z["routed_scale"] = float(c["routed_scaling_factor"])
    return z


def _shape(c: dict):
    z = _routing(c)
    inv, ms = rope_tables(c)
    return (tuple(sorted(z.items())),
            (float(c["rms_norm_eps"]), tuple(float(v) for v in inv),
             float(ms), float(softmax_scale(c))))


@functools.partial(jax.jit, static_argnames=("eps",))
def _gaps(w, x_ref, x_ctl, served, eps):
    """Per row: the reference's best logit minus its logit of the served
    token, and of the token the control puts first. The LM head is read
    in blocks of V_BLOCK columns (V is a multiple of it, or smaller)."""
    head = w["lm_head"]
    V = head.shape[1]
    vb = min(V_BLOCK, V)
    nv = V // vb
    g = w["ln_f"].astype(jnp.float32)
    s_head = jnp.maximum(jnp.max(jnp.abs(head)).astype(jnp.float32),
                         1e-30) / F8_MAX           # the control's one scale

    def rows(args):
        xr, xc, s = args
        hr, hc = _rms(xr, g, eps), _f8_rows(_rms(xc, g, eps))
        neg = jnp.full(xr.shape[:1], -jnp.inf)

        def cols(j, carry):
            best, at_s, c_best, c_at = carry
            wb = jax.lax.dynamic_slice_in_dim(head, j * vb, vb,
                                              axis=1).astype(jnp.float32)
            lr = jnp.matmul(hr, wb, precision=HIGHEST)
            wq = (wb / s_head).astype(F8).astype(jnp.float32) * s_head
            lc = jnp.matmul(hc, wq, precision=HIGHEST)
            col = j * vb + jnp.arange(vb)
            at_s = jnp.maximum(at_s, jnp.max(
                jnp.where(col[None, :] == s[:, None], lr, -jnp.inf), -1))
            i = jnp.argmax(lc, -1)
            lc_max = jnp.take_along_axis(lc, i[:, None], -1)[:, 0]
            lr_at = jnp.take_along_axis(lr, i[:, None], -1)[:, 0]
            newer = lc_max > c_best                 # first maximum wins
            return (jnp.maximum(best, lr.max(-1)), at_s,
                    jnp.where(newer, lc_max, c_best),
                    jnp.where(newer, lr_at, c_at))

        best, at_s, _, c_at = jax.lax.fori_loop(
            0, nv, cols, (neg, neg, neg, neg))
        return best - at_s, best - c_at

    n = x_ref.shape[0] // ROW_BLOCK
    rs = lambda a: a.reshape((n, ROW_BLOCK) + a.shape[1:])  # noqa: E731
    gs, gc = jax.lax.map(rows, (rs(x_ref), rs(x_ctl), rs(served)))
    return gs.reshape(-1), gc.reshape(-1)


def _pad(n: int, m: int) -> int:
    return -(-n // m) * m


def served_gaps(w: Dict[str, jax.Array], c: dict, prompt, served,
                control: bool = False) -> Tuple[np.ndarray, np.ndarray]:
    """Gaps of one served request: for each served token, by how much its
    reference logit lies below the reference's best at that position.
    With `control`, also the gaps of the tokens the float8 forward puts
    first (else that array is empty)."""
    shape = _shape(c)
    seq = list(prompt) + list(served[:-1])
    T = len(seq)
    toks = np.zeros(_pad(T, T_BUCKET), np.int32)
    toks[:T] = seq
    # causal: the padding after the sequence changes no earlier row
    x, tie = _hidden(w, jnp.asarray(toks), shape, EXACT)
    xc = _hidden(w, jnp.asarray(toks), shape, CONTROL)[0] if control else x
    first = len(prompt) - 1
    n = len(served)
    rows = np.arange(first, first + n)
    npad = _pad(n, ROW_BLOCK)
    rows = np.concatenate([rows, np.full(npad - n, first)])
    sv = np.zeros(npad, np.int32)
    sv[:n] = served
    ridx = jnp.asarray(rows)
    gs, gc = _gaps(w, x[ridx], xc[ridx], jnp.asarray(sv),
                   float(c["rms_norm_eps"]))
    gs, gc = np.asarray(gs)[:n], np.asarray(gc)[:n]
    # a routing tie decides nothing: no position whose routing of a held
    # expert lies within ROUTE_TIE of the top-k boundary is compared
    tied = np.asarray(tie)[rows[:n]]
    gs, gc = np.where(tied, 0.0, gs), np.where(tied, 0.0, gc)
    return gs, (gc if control else np.zeros(0))


def full_logits(w: Dict[str, jax.Array], c: dict, tokens) -> np.ndarray:
    """All logits of one short sequence (for tests)."""
    T = len(tokens)
    toks = np.zeros(_pad(T, T_BUCKET), np.int32)
    toks[:T] = tokens
    x = _hidden(w, jnp.asarray(toks), _shape(c), EXACT)[0][:T]
    h = _rms(x, w["ln_f"].astype(jnp.float32), float(c["rms_norm_eps"]))
    return np.asarray(jnp.matmul(h, w["lm_head"].astype(jnp.float32),
                                 precision=HIGHEST))


def expert_layer(w: Dict[str, jax.Array], c: dict, layer: int, h,
                 mode: str = EXACT) -> np.ndarray:
    """The expert layer (routed share + shared expert) of MoE layer
    `layer` on rows h (n, d), at the configuration's held share (for the
    test that ties the shares to the whole layer)."""
    z = _routing(c)
    lw = {k[len("layers/"):]: v[layer].astype(jnp.float32)
          for k, v in w.items() if k.startswith("layers/moe")}
    return np.asarray(_experts(lw, jnp.asarray(h, jnp.float32), z, mode)[0])
