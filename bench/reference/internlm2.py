"""Plain float32 reference: an InternLM2 decoder, optionally behind
image-prefix embeddings, trained by Q-GaLore. It decides ``correct`` for
every cell whose configuration names ``"reference": "internlm2"``.

Written from the published descriptions; it imports nothing of the program
under ``src/`` and takes nothing the program made:

* InternLM2 (arXiv 2403.17297, ``internlm/internlm2-1_8b`` modeling code):
  pre-norm decoder; RMSNorm; rotary embedding, rotate-half, base
  ``rope_theta``; grouped-query attention in which query head ``h`` reads
  kv head ``h // (H / KV)``; feed-forward ``w2(silu(w1 x) * w3 x)``
  (``wd(silu(x wg) * (x wi))`` here); untied output head.
* InternVL2 (arXiv 2404.16821): the vision tower's tokens enter the LLM as
  embeddings ahead of the text; the loss is next-token cross entropy over
  the text positions only.
* Q-GaLore (arXiv 2407.08296), with GaLore (arXiv 2403.03507): weights
  held in INT8 (symmetric, blocks of 256 along the last axis) and updated
  by stochastic rounding; each weight matrix whose two dims are both at
  least 128, the embedding excepted, gets a rank-r projection P on its
  smaller side (``P^T G`` when m < n, ``G P`` otherwise), held in INT4
  (asymmetric, blocks along r) and recomputed at step 0 from the
  gradient; 8-bit Adam on what P gives (on the full gradient elsewhere);
  the update ``-lr * alpha * P dir`` (``dir P^T``); global-norm gradient
  clipping on what the optimizer receives.

Choices the configuration makes, and the reference follows:

* norm gains are stored as offsets from 1 (``y * (1 + g)``, zeros at
  init): the same function as InternLM2's ``y * w`` with ``w = 1 + g``;
* every weight array of two or more dims whose last dim is at least 32 is
  INT8, the per-layer norm gains (stacked over layers) among them; the
  final norm gain, one vector, stays float32;
* the subspace comes from a randomized range finder (Halko et al.,
  oversampling 8, ``subspace_iters`` power iterations with QR), ordered
  by a Rayleigh-Ritz step, because the configuration states
  ``subspace_method: randomized``; the reference draws its own randoms;
* 8-bit Adam keeps ``m`` symmetric and ``sqrt(v)`` asymmetric, in blocks
  of 256 (fewer for a shorter last axis) along the last axis.

All arithmetic is float32 at ``Precision.HIGHEST``. With
``precision="fp8"`` every matrix product instead takes operands rounded to
float8 e4m3 with a per-tensor scale: the control, one step of precision
below the bfloat16 the configuration computes in.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 256            # INT8 weight and 8-bit Adam block
HIGHEST = jax.lax.Precision.HIGHEST
LAYER_KEYS = ("attn_norm", "ffn_norm", "wq", "wk", "wv", "wo", "wi", "wg",
              "wd")
HEAD_CHUNK = 512       # rows of logits formed at once
Q_CHUNK = 1024         # query rows of attention scores formed at once


# ---------------------------------------------------------------------------
# weights, in the layout the system under test takes
# ---------------------------------------------------------------------------

def shapes(model: dict) -> Dict[str, tuple]:
    """Flat name -> shape; per-layer leaves lead with the layer count."""
    L, D, F, V = (model["num_layers"], model["d_model"], model["d_ff"],
                  model["vocab_size"])
    H, KV = model["num_heads"], model["num_kv_heads"]
    hd = model.get("head_dim") or D // H
    return {
        "embedding": (V, D), "head": (D, V), "final_norm": (D,),
        "attn_norm": (L, D), "ffn_norm": (L, D),
        "wq": (L, D, H * hd), "wk": (L, D, KV * hd), "wv": (L, D, KV * hd),
        "wo": (L, H * hd, D),
        "wi": (L, D, F), "wg": (L, D, F), "wd": (L, F, D),
    }


def init_leaf(model: dict, init_std: float, key, name: str):
    """One seeded weight in float32, the type the program quantizes from:
    a matrix N(0, init_std^2) (the published initializer_range), or a
    norm-gain offset of zeros."""
    names = sorted(shapes(model))
    shp = shapes(model)[name]
    if name.endswith("norm"):
        return jnp.zeros(shp, jnp.float32)
    k = jax.random.fold_in(key, names.index(name))
    return jax.random.normal(k, shp, jnp.float32) * init_std


def init_weights(model: dict, init_std: float, key) -> Dict[str, jax.Array]:
    """Every seeded weight. Jit it: one call makes every leaf on the
    device."""
    return {n: init_leaf(model, init_std, key, n) for n in shapes(model)}


def nest(flat: dict) -> dict:
    """Flat names -> the nested tree the system under test takes."""
    seg = {"attn_norm": flat["attn_norm"], "ffn_norm": flat["ffn_norm"],
           "attn": {k: flat[k] for k in ("wq", "wk", "wv", "wo")},
           "ffn": {k: flat[k] for k in ("wi", "wg", "wd")}}
    return {"embedding": flat["embedding"], "head": flat["head"],
            "final_norm": flat["final_norm"], "seg0_dense": seg}


def is_int8(name: str, shape) -> bool:
    return len(shape) >= 2 and shape[-1] >= 32


def galore_side(name: str, shape, min_dim: int = 128):
    """'left' (P^T G), 'right' (G P) or None for a weight without a
    projection: norms, the embedding, and matrices under ``min_dim``."""
    if name == "embedding" or len(shape) < 2:
        return None
    m, n = shape[-2], shape[-1]
    if m < min_dim or n < min_dim:
        return None
    return "left" if m < n else "right"


# ---------------------------------------------------------------------------
# block quantizers
# ---------------------------------------------------------------------------

def _blocks(x, blk):
    pad = (-x.shape[-1]) % blk
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(*x.shape[:-1], x.shape[-1] // blk, blk)


def q8(w, key=None):
    """Symmetric INT8, blocks of 256 along the last axis; stochastic
    rounding floor(t + u) with ``key``, round-to-nearest without."""
    wb = _blocks(w.astype(jnp.float32), BLOCK)
    s = jnp.maximum(jnp.max(jnp.abs(wb), -1, keepdims=True) / 127.0, 1e-12)
    t = wb / s
    if key is None:
        c = jnp.round(t)
    else:
        c = jnp.floor(t + jax.random.uniform(key, t.shape, jnp.float32))
    c = jnp.clip(c, -128, 127).astype(jnp.int8)
    return c.reshape(*c.shape[:-2], -1), s[..., 0]


def dq8(c, s, n: int):
    cb = c.reshape(*c.shape[:-1], -1, BLOCK).astype(jnp.float32)
    return (cb * s[..., None]).reshape(c.shape)[..., :n]


def _moment_block(last: int) -> int:
    if last >= BLOCK:
        return BLOCK
    b = 2
    while b * 2 <= last:
        b *= 2
    return b


def _qsym(x):
    blk = _moment_block(x.shape[-1])
    xb = _blocks(x, blk)
    s = jnp.maximum(jnp.max(jnp.abs(xb), -1, keepdims=True) / 127.0, 1e-12)
    c = jnp.clip(jnp.round(xb / s), -128, 127)
    return (c * s).reshape(*xb.shape[:-2], -1)[..., :x.shape[-1]]


def _qasym(x, bits: int, blk: int):
    """Round trip through asymmetric ``bits``-bit codes (min-max scale,
    float zero point)."""
    qmin, qmax = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    xb = _blocks(x, blk)
    mx, mn = jnp.max(xb, -1, keepdims=True), jnp.min(xb, -1, keepdims=True)
    s = jnp.maximum((mx - mn) / (qmax - qmin), 1e-12)
    z = qmin - mn / s
    c = jnp.clip(jnp.round(xb / s + z), qmin, qmax)
    return ((c - z) * s).reshape(*xb.shape[:-2], -1)[..., :x.shape[-1]]


def store_moments(m, v):
    """8-bit Adam storage: the values the next step reads back."""
    sv = _qasym(jnp.sqrt(v), 8, _moment_block(v.shape[-1]))
    return _qsym(m), sv * sv


def int4_projection(P):
    blk = min(BLOCK, max(2, P.shape[-1]))
    return _qasym(P, 4, blk + blk % 2)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _fp8(x):
    """Round to float8 e4m3 under a per-tensor scale, back in float32.
    The rounding is a value the gradient passes straight through, so the
    backward pass multiplies by the rounded operands in float32 instead
    of underflowing in float8."""
    amax = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30))
    s = 448.0 / amax
    q = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    return x + jax.lax.stop_gradient(q - x)


def _ein(precision: str):
    def ein(spec, a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if precision == "fp8":
            a, b = _fp8(a), _fp8(b)
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    return ein


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs          # (S, half)
    s, c = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(x, w, model, ein):
    B, S, _ = x.shape
    H, KV = model["num_heads"], model["num_kv_heads"]
    hd = model.get("head_dim") or model["d_model"] // H
    G = H // KV
    pos = jnp.arange(S)
    q = _rope(ein("bsd,df->bsf", x, w["wq"]).reshape(B, S, H, hd), pos,
              model["rope_theta"])
    k = _rope(ein("bsd,df->bsf", x, w["wk"]).reshape(B, S, KV, hd), pos,
              model["rope_theta"])
    v = ein("bsd,df->bsf", x, w["wv"]).reshape(B, S, KV, hd)
    q = q.reshape(B, S, KV, G, hd) / math.sqrt(hd)

    @jax.checkpoint
    def rows(q_c, start):
        s = ein("bqkgd,bskd->bkgqs", q_c, k)
        qpos = start + jnp.arange(q_c.shape[1])
        s = jnp.where(pos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return ein("bkgqs,bskd->bqkgd", p, v)

    c = min(Q_CHUNK, S)
    if S % c:
        c = S
    outs = [rows(q[:, i:i + c], i) for i in range(0, S, c)]
    o = jnp.concatenate(outs, 1).reshape(B, S, H * hd)
    return ein("bsf,fd->bsd", o, w["wo"])


def _layer(h, w, model, ein):
    eps = model["rmsnorm_eps"]
    h = h + _attention(_rmsnorm(h, w["attn_norm"], eps), w, model, ein)
    x = _rmsnorm(h, w["ffn_norm"], eps)
    f = jax.nn.silu(ein("bsd,df->bsf", x, w["wg"])) \
        * ein("bsd,df->bsf", x, w["wi"])
    return h + ein("bsf,fd->bsd", f, w["wd"])


def _cross_entropy(h_text, labels, head, ein):
    """Mean next-token cross entropy, logits formed HEAD_CHUNK rows at a
    time: position t predicts label t + 1."""
    B, S, D = h_text.shape
    x = h_text[:, :-1].reshape(-1, D)
    y = labels[:, 1:].reshape(-1)
    n = x.shape[0]
    pad = (-n) % HEAD_CHUNK
    x = jnp.pad(x, ((0, pad), (0, 0)))
    y = jnp.pad(y, (0, pad))
    valid = (jnp.arange(n + pad) < n).astype(jnp.float32)

    @jax.checkpoint
    def chunk(args):
        xc, yc, vc = args
        logits = ein("nd,dv->nv", xc, head)
        lse = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, yc[:, None], -1)[:, 0]
        return jnp.sum((lse - gold) * vc)

    parts = jax.lax.map(chunk, (x.reshape(-1, HEAD_CHUNK, D),
                                y.reshape(-1, HEAD_CHUNK),
                                valid.reshape(-1, HEAD_CHUNK)))
    return jnp.sum(parts) / n


# ---------------------------------------------------------------------------
# Q-GaLore
# ---------------------------------------------------------------------------

def randomized_subspace(G, rank: int, side: str, key, iters: int):
    """Top-``rank`` singular subspace of G on ``side`` (d, rank)."""
    A = G if side == "left" else G.T
    d, k = A.shape
    p = min(rank + 8, k)
    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    Y = mm(A, jax.random.normal(key, (k, p), jnp.float32))
    for _ in range(iters):
        Y = jnp.linalg.qr(Y)[0]
        Y = mm(A, mm(A.T, Y))
    Q = jnp.linalg.qr(Y)[0]
    B = mm(Q.T, A)
    _, U = jnp.linalg.eigh(mm(B, B.T))
    return mm(Q, U[:, ::-1][:, :rank])


def _project(G, P, side):
    if side == "left":
        return jnp.matmul(P.T, G, precision=HIGHEST)
    return jnp.matmul(G, P, precision=HIGHEST)


def _back(L, P, side):
    if side == "left":
        return jnp.matmul(P, L, precision=HIGHEST)
    return jnp.matmul(L, P.T, precision=HIGHEST)


class Reference:
    """Q-GaLore steps from seeded weights and batches, one layer at a time
    so that it fits one chip: the forward keeps each layer's input, the
    backward runs each layer's VJP from it and keeps the float32
    gradients, which the clip needs whole before any update; the optimizer
    then updates one weight of one layer at a time."""

    def __init__(self, model: dict, qgalore: dict, init_std: float,
                 precision: str = "f32"):
        if qgalore.get("weight_decay"):
            raise ValueError("the reference has no weight decay")
        self.model, self.q = model, qgalore
        self.shapes = shapes(model)
        self.L = model["num_layers"]
        self.ein = _ein(precision)
        self._init = {n: jax.jit(functools.partial(init_leaf, model,
                                                   init_std, name=n))
                      for n in self.shapes}
        self._fwd = jax.jit(self._fwd_fn)
        self._bwd = jax.jit(self._bwd_fn)
        self._head = jax.jit(self._head_fn, static_argnums=4)
        self._embed = jax.jit(self._embed_fn)
        self._embed_bwd = jax.jit(self._embed_bwd_fn)
        self._sq = {}
        self._step = {}
        self._q8 = jax.jit(q8)
        self._diff = {n: jax.jit(functools.partial(self._diff_fn, n))
                      for n in self.shapes}

    # -- weights ----------------------------------------------------------
    def side(self, name):
        return galore_side(name, self.shapes[name], self.q["min_dim"])

    def initial(self, wkey) -> Dict[str, list]:
        """name -> one leaf per layer (a single leaf for weights outside
        the layers); a leaf is (codes, scales) in INT8 or (value,)."""
        out = {}
        for name, shp in self.shapes.items():
            x = self._init[name](wkey)
            parts = [x[i] for i in range(shp[0])] if name in _PER_LAYER \
                else [x]
            del x
            out[name] = [self._q8(p) if is_int8(name, shp) else (p,)
                         for p in parts]
        return out

    def value(self, name, leaf):
        if len(leaf) == 1:
            return leaf[0]
        return dq8(leaf[0], leaf[1], self.shapes[name][-1])

    # -- the model, one piece at a time ------------------------------------
    def _wl(self, wl):
        return {k: self.value(k, v) for k, v in wl.items()}

    def _fwd_fn(self, h, wl):
        return _layer(h, self._wl(wl), self.model, self.ein)

    def _bwd_fn(self, h, wl, ct):
        out, vjp = jax.vjp(
            lambda h, w: _layer(h, w, self.model, self.ein), h, self._wl(wl))
        return vjp(ct)

    def _embed_fn(self, emb, batch):
        h = jnp.take(self.value("embedding", emb), batch["tokens"], axis=0)
        if "patch_embeds" in batch:
            h = jnp.concatenate(
                [batch["patch_embeds"].astype(jnp.float32), h], 1)
        return h

    def _embed_bwd_fn(self, emb, batch, ct):
        E = self.value("embedding", emb)
        n_prefix = ct.shape[1] - batch["tokens"].shape[1]
        _, vjp = jax.vjp(lambda E: jnp.take(E, batch["tokens"], axis=0), E)
        return vjp(ct[:, n_prefix:])[0]

    def _head_fn(self, h, fnorm, head, labels, n_prefix: int):
        def f(h, g, w):
            x = _rmsnorm(h, g, self.model["rmsnorm_eps"])
            return _cross_entropy(x[:, n_prefix:], labels, w, self.ein)
        loss, grads = jax.value_and_grad(f, argnums=(0, 1, 2))(
            h, self.value("final_norm", fnorm), self.value("head", head))
        return loss, grads

    def loss_and_grads(self, W, batch):
        """The loss, and every weight's float32 gradient (per layer)."""
        L = self.L
        h = [self._embed(W["embedding"][0], batch)]
        for layer in range(L):
            h.append(self._fwd(h[-1], {k: W[k][layer] for k in LAYER_KEYS}))
        n_prefix = h[0].shape[1] - batch["tokens"].shape[1]
        loss, (ct, g_fn, g_head) = self._head(
            h[L], W["final_norm"][0], W["head"][0], batch["labels"],
            n_prefix)
        G = {k: [None] * L for k in LAYER_KEYS}
        for layer in reversed(range(L)):
            h[layer + 1] = None
            ct, gw = self._bwd(h[layer], {k: W[k][layer]
                                          for k in LAYER_KEYS}, ct)
            for k in LAYER_KEYS:
                G[k][layer] = gw[k]
        G["embedding"] = [self._embed_bwd(W["embedding"][0], batch, ct)]
        G["final_norm"], G["head"] = [g_fn], [g_head]
        return float(loss), G

    # -- optimizer --------------------------------------------------------
    def _sq_fn(self, name: str, refresh: bool):
        """Squared norm of what one weight's optimizer receives: the
        full-rank gradient at the refresh step, the projected one on the
        others."""
        side = self.side(name)

        def sq(g, P):
            if side and not refresh:
                g = _project(g, P, side)
            return jnp.sum(g * g)
        return jax.jit(sq)

    def _step_fn(self, name: str, refresh: bool):
        shp = self.shapes[name]
        side = self.side(name)
        q = self.q
        b1, b2, eps = q["beta1"], q["beta2"], q["eps"]
        rank = min(q["rank"], *shp[-2:]) if side else 0

        def one(leaf, g, m, v, P, count, lr, scale, key):
            """One weight of one layer. Returns the new weight, moments, P
            and the norm of the gradient Adam received."""
            g = g * scale
            kr, ks = jax.random.split(key)
            if side:
                if refresh:
                    P = int4_projection(randomized_subspace(
                        g, rank, side, kr, q["subspace_iters"]))
                g = _project(g, P, side)
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            c = count.astype(jnp.float32)
            d = (m / (1 - b1 ** c)) / (jnp.sqrt(v / (1 - b2 ** c)) + eps)
            if side:
                d = q["scale"] * _back(d, P, side)
            m, v = store_moments(m, v)
            if len(leaf) == 1:
                new = (leaf[0] - lr * d,)
            else:
                new = q8(dq8(leaf[0], leaf[1], shp[-1]) - lr * d, ks)
            return new, m, v, P, jnp.sqrt(jnp.sum(g * g))

        return jax.jit(one)

    def _opt_state(self, name):
        """Zero moments and a placeholder P, per layer, for one weight."""
        shp = self.shapes[name]
        side = self.side(name)
        one = shp[1:] if name in _PER_LAYER else shp
        n = self.L if name in _PER_LAYER else 1
        if side:
            m_, n_ = one[-2:]
            r = min(self.q["rank"], m_, n_)
            low = (r, n_) if side == "left" else (m_, r)
            d = m_ if side == "left" else n_
            P = jnp.zeros((d, r), jnp.float32)
        else:
            low, P = one, jnp.zeros((0,), jnp.float32)
        z = jnp.zeros(low, jnp.float32)
        return [[z, z, P] for _ in range(n)]

    def run(self, wkey, batches: List[dict], lrs: List[float], key,
            steps: int = 3) -> dict:
        """Run ``steps`` steps from the seeded weights. Returns the loss of
        each step, each weight's gradient as Adam received it at the first
        step (``grad``, the refresh) and, for the weights without a
        projection, at the second (``grad1``, a plain step), and each
        weight's change over the steps (per layer)."""
        W = self.initial(wkey)
        opt = {k: self._opt_state(k) for k in self.shapes}
        names = sorted(self.shapes)
        losses, grads = [], [{} for _ in range(steps)]
        for step in range(steps):
            loss, G = self.loss_and_grads(W, batches[step])
            losses.append(loss)
            refresh = step == 0
            sq = jnp.float32(0)
            for name in names:
                fn = self._sq.get((name, refresh))
                if fn is None:
                    fn = self._sq[(name, refresh)] = self._sq_fn(name,
                                                                 refresh)
                for g, st in zip(G[name], opt[name]):
                    sq = sq + fn(g, st[2])
            norm = math.sqrt(float(sq))
            scale = jnp.float32(min(1.0, self.q["grad_clip"]
                                    / (norm + 1e-6)))
            for i, name in enumerate(names):
                fn = self._step.get((name, refresh))
                if fn is None:
                    fn = self._step[(name, refresh)] = self._step_fn(
                        name, refresh)
                gns = []
                for layer, (g, st) in enumerate(zip(G[name], opt[name])):
                    k = jax.random.fold_in(jax.random.fold_in(
                        jax.random.fold_in(key, step), i), layer)
                    new, m, v, P, gn = fn(
                        W[name][layer], g, st[0], st[1], st[2],
                        jnp.int32(step + 1), jnp.float32(lrs[step]),
                        scale, k)
                    W[name][layer], opt[name][layer] = new, [m, v, P]
                    G[name][layer] = None
                    gns.append(gn)
                grads[step][name] = np.asarray(jnp.stack(gns))
            del G
        del opt
        return {"losses": losses, "grad": grads[0],
                "grad1": {n: grads[1][n] for n in names
                          if self.side(n) is None},
                "change": self.change(W, self.initial(wkey))}

    def _diff_fn(self, name, a, b):
        d = self.value(name, a) - self.value(name, b)
        return jnp.sqrt(jnp.sum(d * d))

    def change(self, W, W0) -> dict:
        """Per weight (per layer), the norm of W - W0."""
        return {name: np.asarray(jnp.stack([
            self._diff[name](a, b) for a, b in zip(W[name], W0[name])]))
            for name in W}


_PER_LAYER = frozenset(LAYER_KEYS)
