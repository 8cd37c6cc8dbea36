"""Plain reference of the dense decoder LM that both configurations run.

Straightforward ``jax.numpy``: every TT matrix is first multiplied out to
its dense ``(d_in, d_out)`` weight, every matmul runs in float32 at
``Precision.HIGHEST``, and there is no kernel, cache, bucket or batching.
It imports nothing of the program and reads only the parameter tree the
benchmark made from the seed.

The architecture, as the configurations state it:

  x = E[token]                                  (TT-matrix embedding)
  per layer:  x += Wo(attn(rope(Wq h + bq), rope(Wk h + bk), Wv h + bv))
              x += Wd(silu(Wg h') * Wu h')      h = rms(x) g1, h' = rms(x) g2
  logits = rms(x) g_f E^T                       (head tied to the embedding)

with causal grouped-query attention (query head j reads key/value head
j mod n_kv), RMSNorm (eps 1e-6), and rotary embedding on the first
``rope_fraction`` of each head in rotate-half form (base 10000).

Departures from the published ChatGLM3-6B, all of them the program's
configuration and not this file's choice: the head is tied to the
embedding (ChatGLM3 has an untied output layer), and its partial rotary
embedding rotates halves where ChatGLM pairs neighbouring dimensions, a
fixed permutation of the rotated dimensions.

``mode`` selects the precision: ``"f32"`` is the reference; ``"bf16"``
and ``"fp8"`` are the controls (matmul operands rounded to bfloat16, or
to e4m3 with one scale per tensor, and activations kept in bfloat16).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

F32, BF16 = jnp.float32, jnp.bfloat16
HI = jax.lax.Precision.HIGHEST
Q_CHUNK = 1024


@dataclasses.dataclass(frozen=True)
class Arch:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    vocab: int
    rope_fraction: float
    tt_d: int
    rope_base: float = 10_000.0
    eps: float = 1e-6


def arch_of(config: dict) -> Arch:
    s = config["sizes"]
    return Arch(
        n_layers=s["n_layers"], d_model=s["d_model"], n_heads=s["n_heads"],
        n_kv_heads=s["n_kv_heads"],
        head_dim=s.get("head_dim") or s["d_model"] // s["n_heads"],
        vocab=s["vocab"],
        rope_fraction={"full": 1.0, "glm2d": 0.5, "none": 0.0}[s["rope"]],
        tt_d=config["tt_factorization"]["d"])


# -- precision -----------------------------------------------------------

def act_dtype(mode: str):
    return F32 if mode == "f32" else BF16


def _fp8(x):
    x = x.astype(F32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def mm(x, w, mode: str, eq: str = "...k,kn->...n"):
    """One matmul in ``mode``; the result in the mode's activation type."""
    if mode == "f32":
        return jnp.einsum(eq, x.astype(F32), w.astype(F32), precision=HI)
    if mode == "bf16":
        return jnp.einsum(eq, x.astype(BF16), w.astype(BF16),
                          preferred_element_type=F32).astype(BF16)
    return jnp.einsum(eq, _fp8(x), _fp8(w), precision=HI).astype(BF16)


# -- weights -------------------------------------------------------------

def tt_matrix(cores: list, n_out: int):
    """Dense ``(d_in, d_out)`` float32 weight of a TT matrix whose cores
    are ``(m, r)``, ``(r, m, r)`` ... ``(r, m)``, the first ``n_out``
    carrying output modes (big-endian mode order on both sides)."""
    full = cores[0].astype(F32)
    for c in cores[1:]:
        full = jnp.tensordot(full, c.astype(F32), axes=([-1], [0]),
                             precision=HI)
    modes = full.shape
    d_out = math.prod(modes[:n_out])
    return full.reshape(d_out, -1).T


def weight(p: dict, n_out: int):
    if "w" in p:
        return p["w"].astype(F32)
    n = sum(1 for k in p if k.startswith("core"))
    return tt_matrix([p[f"core{k}"] for k in range(n)], n_out)


def embed_table(p: dict):
    """Dense ``(vocab, d_model)`` table of a TT embedding whose cores are
    ``(r, v, d, r)``."""
    if "table" in p:
        return p["table"].astype(F32)
    n = sum(1 for k in p if k.startswith("core"))
    full = p["core0"].astype(F32)[0]                  # (v1, d1, r1)
    vs, ds = [full.shape[0]], [full.shape[1]]
    for k in range(1, n):
        c = p[f"core{k}"].astype(F32)                 # (r, v, d, r')
        full = jnp.tensordot(full, c, axes=([-1], [0]), precision=HI)
        vs.append(c.shape[1])
        ds.append(c.shape[2])
    full = full[..., 0]                               # (v1, d1, ..., vn, dn)
    perm = [2 * k for k in range(n)] + [2 * k + 1 for k in range(n)]
    return full.transpose(perm).reshape(math.prod(vs), math.prod(ds))


# -- layers --------------------------------------------------------------

def rmsnorm(x, scale, eps, mode):
    x32 = x.astype(F32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(F32)).astype(act_dtype(mode))


def rope(x, positions, arch: Arch):
    """Rotate-half rotary embedding on the first ``rope_fraction`` of each
    head; ``x`` is (B, T, H, Dh), ``positions`` (T,)."""
    rot = int(arch.head_dim * arch.rope_fraction)
    rot -= rot % 2
    if rot == 0:
        return x
    half = rot // 2
    inv = 1.0 / (arch.rope_base ** (jnp.arange(half, dtype=F32) / half))
    ang = positions.astype(F32)[:, None] * inv          # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1 = x[..., :half].astype(F32)
    x2 = x[..., half:rot].astype(F32)
    r = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return jnp.concatenate([r.astype(x.dtype), x[..., rot:]], -1)


def attention(q, k, v, arch: Arch, mode: str):
    """Causal grouped-query attention over (B, T, H, Dh) queries."""
    b, t, h, dh = q.shape
    hkv = arch.n_kv_heads
    rep = jnp.arange(h) % hkv
    k, v = k[:, :, rep], v[:, :, rep]                  # (B, T, H, Dh)
    outs = []
    for s0 in range(0, t, Q_CHUNK):
        qc = q[:, s0:s0 + Q_CHUNK]
        sc = mm(qc, k, mode, "bqhd,bkhd->bhqk").astype(F32) / math.sqrt(dh)
        qpos = s0 + jnp.arange(qc.shape[1])
        mask = jnp.arange(t)[None, :] <= qpos[:, None]
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(mm(p, v, mode, "bhqk,bkhd->bqhd"))
    return jnp.concatenate(outs, axis=1)


def block(x, lp, positions, arch: Arch, mode: str):
    b, t, _ = x.shape
    n = arch.tt_d
    at = lp["attn"]
    h = rmsnorm(x, lp["ln1"]["scale"], arch.eps, mode)

    def proj(name, heads):
        y = mm(h, weight(at[name], n), mode)
        if "b" in at[name]:
            y = y + at[name]["b"].astype(y.dtype)
        return y.reshape(b, t, heads, arch.head_dim)

    q = rope(proj("wq", arch.n_heads), positions, arch)
    k = rope(proj("wk", arch.n_kv_heads), positions, arch)
    v = proj("wv", arch.n_kv_heads)
    o = attention(q, k, v, arch, mode).reshape(b, t, -1)
    x = x + mm(o, weight(at["wo"], n), mode)
    ml = lp["mlp"]
    h = rmsnorm(x, lp["ln2"]["scale"], arch.eps, mode)
    g = mm(h, weight(ml["wg"], n), mode)
    u = mm(h, weight(ml["wu"], n), mode)
    a = (jax.nn.silu(g.astype(F32)) * u.astype(F32)).astype(act_dtype(mode))
    return x + mm(a, weight(ml["wd"], n), mode)


def hidden(params, tokens, arch: Arch, mode: str):
    """Final normed hidden states (B, T, D) of token rows (B, T)."""
    table = embed_table(params["embed"])
    x = table[tokens].astype(act_dtype(mode))
    positions = jnp.arange(tokens.shape[1])

    def body(x, lp):
        return block(x, lp, positions, arch, mode), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    return rmsnorm(x, params["ln_f"]["scale"], arch.eps, mode), table


def logits_at(params, tokens, rows, cols, arch: Arch, mode: str):
    """Float32 logits at (row, position) pairs of token rows (B, T)."""
    h, table = hidden(params, tokens, arch, mode)
    return mm(h[rows, cols], table, mode, "nd,vd->nv").astype(F32)


def loss(params, tokens, labels, arch: Arch, mode: str):
    """Sum of token cross-entropies of rows (B, T) (divide for the mean)."""
    h, table = hidden(params, tokens, arch, mode)
    lg = mm(h, table, mode, "btd,vd->btv").astype(F32)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - ll)
