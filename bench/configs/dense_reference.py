"""Plain float32 reference for the dense decoder configurations.

Straightforward ``jax.numpy`` at ``highest`` matmul precision: one stack of
layers, no slots, no pipeline, no kernels.  It is written from the
definitions below and imports nothing of the system under test.

Model (per layer i, x the residual stream [b, s, d]):
  h  = rmsnorm(x) * attn_norm
  q, k, v = h Wq, h Wk, h Wv, RoPE (rotate-half, theta) on q and k
  mask: when s >= 2 * block, the hash block mask of h (below); else causal
  x += softmax(q k^T / sqrt(hd), causal & mask) v Wo     (GQA: q head j
       reads kv head j // (n_q / n_kv))
  x += (silu(g Wg) * (g Wi)) Wof,  g = rmsnorm(x) * ffn_norm
loss = mean over tokens of logsumexp(z) - z[label], z = rmsnorm(x) * fn @ head

Hash block mask (dynamic sparse attention): split h into blocks of
``block`` tokens, average each block, project the mean on a fixed
``normal(PRNGKey(17), [d, nbits])`` matrix (nbits = bit length of
nbuckets - 1) and read the signs as a bucket id modulo nbuckets.  Query
block qb attends key block kb when their buckets match or |qb - kb| <= 1,
and kb <= qb.  The projection is taken at the matmul precision the cell
states (``matmul_precision``), as the system takes it; the rest of the
model at ``highest``.

A sign read this close to its plane flips under rounding: two sound
computations of the same model may disagree on it.  So where the system's
live-tile count of a layer and step is given (a step of one sequence), the
reference explains it: of the ``FLIP_BITS`` hash bits nearest their planes
(relative margin |m . p| / (|m| |p|)), it flips the subset that gives the
system's count and lies nearest the planes (the least largest margin, then
the fewest flips), and computes the layer under that mask.  The largest
margin so crossed is the layer's ``needed`` margin; a count that no subset
gives reads 1 (no rounding explains it).  The loss and gradients are then
compared under one mask, and the margin is compared on its own.

Weights from the seed: key = PRNGKey(seed) splits into (embed, head,
layers, spare); the layers key splits into (per-layer, spare), and the
per-layer key into one key per layer.  A layer's key splits into one key per
weight, in the order of the weights' sorted names; norm scales are ones and
a matrix [.., fan_in, fan_out] is normal * fan_in ** -0.5.  The embedding
is normal * 0.02 and the head normal * d ** -0.5; the final norm is ones.

Optimizer, the trainer's rule: AdamW (``ADAMW``) after clipping the
gradient to global norm 1; weight decay on every per-layer weight (the norm
scales included), the embedding and the head, and not on the final norm;
the learning rate rises linearly to ``LR_PEAK`` over ``LR_WARMUP`` steps
(the run's horizon is never reached, so no decay starts).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

FIELDS = ("attn_norm", "ffn_norm", "wg", "wi", "wk", "wo", "wof", "wq", "wv")
HASH_KEY = 17
FLIP_BITS = 10
ADAMW = {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
         "clip_norm": 1.0}
LR_PEAK, LR_WARMUP = 3e-4, 10


def dims(c: dict) -> dict:
    return dict(d=c["hidden_size"], ff=c["intermediate_size"],
                nq=c["num_attention_heads"], nkv=c["num_key_value_heads"],
                hd=c["head_dim"], V=c["vocab_size"], L=c["num_hidden_layers"],
                theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]))


def layer_shapes(c: dict) -> Dict[str, tuple]:
    m = dims(c)
    d, ff, q, kv = m["d"], m["ff"], m["nq"] * m["hd"], m["nkv"] * m["hd"]
    return {"attn_norm": (d,), "ffn_norm": (d,), "wg": (d, ff),
            "wi": (d, ff), "wk": (d, kv), "wo": (q, d), "wof": (ff, d),
            "wq": (d, q), "wv": (d, kv)}


def leaf_names(c: dict) -> List[str]:
    """The leaves compared one by one: whole-model tensors and each layer's
    weights."""
    L = dims(c)["L"]
    return (["embed", "head", "final_norm"]
            + [f"layer{i}.{f}" for i in range(L) for f in FIELDS])


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key, frozen_c):
    c = dict(frozen_c)
    m = dims(c)
    shapes = layer_shapes(c)
    k_emb, k_head, k_layers, _ = jax.random.split(key, 4)
    k_per_layer, _ = jax.random.split(k_layers)

    def one(k):
        out = {}
        for kk, name in zip(jax.random.split(k, len(FIELDS)), FIELDS):
            shp = shapes[name]
            if name.endswith("norm"):
                out[name] = jnp.ones(shp, jnp.float32)
            else:
                out[name] = (jax.random.normal(kk, shp, jnp.float32)
                             * shp[-2] ** -0.5)
        return out

    return {
        "embed": jax.random.normal(k_emb, (m["V"], m["d"]), jnp.float32)
        * 0.02,
        "head": jax.random.normal(k_head, (m["d"], m["V"]), jnp.float32)
        * m["d"] ** -0.5,
        "final_norm": jnp.ones((m["d"],), jnp.float32),
        "layers": jax.vmap(one)(jax.random.split(k_per_layer, m["L"])),
    }


def _freeze(c: dict):
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str, bool))))


def init_params(seed: int, c: dict):
    return _init(jax.random.PRNGKey(seed), _freeze(c))


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------
def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rope(x, theta):
    """x: [b, s, h, hd]; rotate-half RoPE at positions 0..s-1."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs    # [s, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def hash_bits(h, nbuckets: int, block: int, precision: str):
    """h: [b, s, d] -> (bits, margins), each [b, nb, nbits]: the signs of
    the block means' projections and their relative margins."""
    b, s, d = h.shape
    nb = s // block
    mean = h[:, :nb * block].reshape(b, nb, block, d).mean(axis=2)
    nbits = max(1, int(nbuckets - 1).bit_length())
    proj = jax.random.normal(jax.random.PRNGKey(HASH_KEY), (d, nbits),
                             jnp.float32)
    z = jnp.einsum("bnd,dk->bnk", mean, proj, precision=precision)
    scale = (jnp.linalg.norm(mean, axis=-1)[..., None]
             * jnp.linalg.norm(proj, axis=0))
    return z > 0, jnp.abs(z) / jnp.maximum(scale, 1e-30)


def block_mask_of(bits, nbuckets: int):
    """bits: [..., nb, nbits] -> bool [..., nb, nb] (causal at block
    level)."""
    nb, nbits = bits.shape[-2:]
    bucket = jnp.sum(bits * (2 ** jnp.arange(nbits)), axis=-1) % nbuckets
    idx = jnp.arange(nb)
    same = bucket[..., :, None] == bucket[..., None, :]
    near = jnp.abs(idx[:, None] - idx[None, :]) <= 1
    return (same | near) & (idx[:, None] >= idx[None, :])


def explained_mask(bits, margins, nbuckets: int, target):
    """The mask of one sequence ([nb, nbits] bits and margins) whose live
    tiles number ``target``, by the flips of the ``FLIP_BITS`` bits nearest
    their planes that lie nearest the planes; ``target`` < 0: the
    reference's own mask.  Returns (mask [nb, nb], needed margin)."""
    flat_b, flat_m = bits.reshape(-1), margins.reshape(-1)
    k = min(FLIP_BITS, flat_m.shape[0])
    near = jnp.argsort(flat_m)[:k]
    subsets = ((jnp.arange(2 ** k)[:, None] >> jnp.arange(k)) & 1) == 1
    flips = jnp.zeros((2 ** k, flat_b.shape[0]), bool).at[:, near].set(
        subsets)
    masks = block_mask_of((flat_b ^ flips).reshape((2 ** k,) + bits.shape),
                          nbuckets)
    counts = jnp.sum(masks, axis=(1, 2))
    cost = jnp.max(jnp.where(subsets, flat_m[near], 0.0), axis=1)
    cost = jnp.where(counts == target, cost, jnp.inf)
    best = jnp.min(cost)
    pick = jnp.argmin(jnp.where(cost == best, jnp.sum(subsets, axis=1),
                                2 ** k))
    found = jnp.isfinite(best) & (target >= 0)
    mask = jnp.where(found, masks[pick], masks[0])
    needed = jnp.where(target < 0, 0.0, jnp.where(found, best, 1.0))
    return mask, needed


def attention(q, k, v, block_mask, block: int):
    """q: [b, s, nq, hd]; k, v: [b, s, nkv, hd]; block_mask: bool
    [b, nb, nb] or None (plain causal).  Computed one query block at a time
    so that the scores of a whole sequence never live at once."""
    b, s, nq, hd = q.shape
    rep = nq // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    nqb = s // block
    cols = jnp.arange(s)

    @jax.checkpoint
    def one(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * block, block, axis=1)
        sc = jnp.einsum("bqhd,bkhd->bhqk", qi, k) / jnp.sqrt(
            jnp.float32(hd))
        rows = i * block + jnp.arange(block)
        ok = rows[:, None] >= cols[None, :]                      # [bq, s]
        if block_mask is not None:
            live = jnp.repeat(block_mask[:, i], block, axis=-1)  # [b, s]
            ok = ok[None] & live[:, None, :]                     # [b, bq, s]
            ok = ok[:, None]
        else:
            ok = ok[None, None]
        sc = jnp.where(ok, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(one, jnp.arange(nqb))             # [nqb, b, bq, h, hd]
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, nq, hd)


def layer(x, p, target, c: dict, sparse: Optional[dict]):
    """One layer; ``target`` is the system's live-tile count of this layer
    (< 0: none given).  Returns (x, live share of the reference's own mask,
    needed margin)."""
    m = dims(c)
    b, s, _ = x.shape
    h = rms_norm(x, p["attn_norm"], m["eps"])
    q = rope((h @ p["wq"]).reshape(b, s, m["nq"], m["hd"]), m["theta"])
    k = rope((h @ p["wk"]).reshape(b, s, m["nkv"], m["hd"]), m["theta"])
    v = (h @ p["wv"]).reshape(b, s, m["nkv"], m["hd"])
    mask, block = None, min(s, 512)
    live, needed = jnp.float32(1.0), jnp.float32(0.0)
    if sparse is not None and s >= 2 * sparse["block"]:
        block = sparse["block"]
        bits, margins = hash_bits(h, sparse["nbuckets"], block,
                                  sparse["precision"])
        mask = block_mask_of(bits, sparse["nbuckets"])
        nb = mask.shape[-1]
        live = jnp.sum(mask) / (b * nb * (nb + 1) / 2)
        if b == 1:
            m0, needed = explained_mask(bits[0], margins[0],
                                        sparse["nbuckets"], target)
            mask = m0[None]
    while s % block:
        block //= 2
    a = attention(q, k, v, mask, block)
    x = x + a.reshape(b, s, m["nq"] * m["hd"]) @ p["wo"]
    g = rms_norm(x, p["ffn_norm"], m["eps"])
    x = x + (jax.nn.silu(g @ p["wg"]) * (g @ p["wi"])) @ p["wof"]
    return x, live, needed


def nll_sum(params, tokens, labels, weights, targets, c: dict, sparse,
            chunk: int):
    """Summed token loss of one micro-batch [b, s], and per layer the mask
    density (live tiles over causal tiles) and the needed margin."""
    m = dims(c)
    x = params["embed"][tokens]

    def body(x, pt):
        x, live, needed = jax.checkpoint(
            functools.partial(layer, c=c, sparse=sparse))(x, *pt)
        return x, (live, needed)

    x, density = jax.lax.scan(body, x, (params["layers"], targets))
    hn = rms_norm(x, params["final_norm"], m["eps"]).reshape(-1, m["d"])
    lab = labels.reshape(-1)
    w = weights.reshape(-1)
    n = hn.shape[0] // chunk

    @jax.checkpoint
    def piece(i):
        hi = jax.lax.dynamic_slice_in_dim(hn, i * chunk, chunk)
        li = jax.lax.dynamic_slice_in_dim(lab, i * chunk, chunk)
        wi = jax.lax.dynamic_slice_in_dim(w, i * chunk, chunk)
        z = hi @ params["head"]
        ll = jnp.take_along_axis(z, li[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(z, axis=-1) - ll) * wi)

    return jnp.sum(jax.lax.map(piece, jnp.arange(n))), density


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------
def _decays(path) -> bool:
    return not any(getattr(k, "key", None) == "final_norm" for k in path)


def adamw_step(params, opt, grads, lr, t, o: dict = ADAMW):
    leaves = jax.tree.leaves(grads)
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(gn, 1e-12))
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = o["b1"], o["b2"]
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, opt["m"], grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, opt["v"],
                     grads)

    def upd(path, p, m_, v_):
        u = (m_ / (1 - b1 ** t)) / (jnp.sqrt(v_ / (1 - b2 ** t)) + o["eps"])
        if _decays(path):
            u = u + o["weight_decay"] * p
        return p - lr * u

    params = jax.tree_util.tree_map_with_path(upd, params, m, v)
    return params, {"m": m, "v": v}, grads


def leaf_norms(tree, c: dict) -> Dict[str, jax.Array]:
    """Per-leaf L2 norms in ``leaf_names`` order (a dict of device
    scalars / vectors)."""
    sq = lambda a, axes: jnp.sqrt(jnp.sum(a * a, axis=axes))
    out = {k: sq(tree[k], None) for k in ("embed", "head", "final_norm")}
    for f in FIELDS:
        a = tree["layers"][f]
        out[f] = sq(a, tuple(range(1, a.ndim)))
    return out


def _flatten_norms(norms, c: dict) -> Dict[str, float]:
    host = jax.device_get(norms)
    out = {k: float(host[k]) for k in ("embed", "head", "final_norm")}
    for i in range(dims(c)["L"]):
        for f in FIELDS:
            out[f"layer{i}.{f}"] = float(host[f][i])
    return out


def lr_at(step: int) -> float:
    return LR_PEAK * min(1.0, (step + 1) / LR_WARMUP)


def run(seed: int, c: dict, batches: Sequence[dict], sparse: Optional[dict],
        targets=None, *, chunk: int = 2048) -> dict:
    """Train ``len(batches)`` steps from the seed.  ``targets`` [step,
    layer]: the system's live-tile counts where a step holds one sequence
    (else -1).  Returns each step's loss, the per-leaf norms of the first
    (clipped) gradient, the per-leaf norms of the change of the parameters
    over all steps, the mean mask density per layer of the reference's own
    masks, and per step and layer the needed margin."""
    L = dims(c)["L"]
    if targets is None:
        targets = -np.ones((len(batches), L), np.int32)
    with jax.default_matmul_precision("highest"):
        params = init_params(seed, c)
        p0 = jax.tree.map(jnp.copy, params)
        opt = {"m": jax.tree.map(jnp.zeros_like, params),
               "v": jax.tree.map(jnp.zeros_like, params)}

        @jax.jit
        def micro_grad(params, tok, lab, w, tgt):
            ch = min(chunk, tok.shape[-1])
            (nll, aux), g = jax.value_and_grad(
                lambda p: nll_sum(p, tok, lab, w, tgt, c, sparse, ch),
                has_aux=True)(params)
            return nll, g, aux

        add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                      donate_argnums=(0,))

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def update(params, opt, gsum, nll, n, lr, t):
            grads = jax.tree.map(lambda g: g / n, gsum)
            params, opt, grads = adamw_step(params, opt, grads, lr, t)
            return params, opt, nll / n, leaf_norms(grads, c)

        def step(params, opt, batch, lr, t, tgt):
            """One step over a batch [m, b, s], a micro-batch at a time."""
            m = batch["tokens"].shape[0]
            gsum, nll, dens, needed = None, 0.0, [], []
            for j in range(m):
                nj, gj, (dj, ej) = micro_grad(
                    params, batch["tokens"][j], batch["labels"][j],
                    batch["label_mask"][j],
                    tgt if m == 1 else -jnp.ones_like(tgt))
                gsum = gj if gsum is None else add(gsum, gj)
                nll = nll + nj
                dens.append(dj)
                needed.append(ej)
            n = jnp.maximum(jnp.sum(batch["label_mask"]), 1.0)
            params, opt, loss, gn = update(params, opt, gsum, nll, n, lr, t)
            return (params, opt, loss, gn, jnp.stack(dens),
                    jnp.max(jnp.stack(needed), axis=0))

        diff = jax.jit(lambda a, b: leaf_norms(
            jax.tree.map(jnp.subtract, a, b), c))
        losses, densities, needed, g0 = [], [], [], None
        for i, b in enumerate(batches):
            b = {k: jnp.asarray(b[k]) for k in ("tokens", "labels",
                                                 "label_mask")}
            params, opt, loss, gn, dens, need = step(
                params, opt, b, jnp.float32(lr_at(i)), jnp.float32(i + 1),
                jnp.asarray(targets[i], jnp.int32))
            losses.append(float(loss))
            densities.append(np.asarray(dens))
            needed.append(np.asarray(need))
            if g0 is None:
                g0 = _flatten_norms(gn, c)
        delta = _flatten_norms(diff(params, p0), c)
    dens = np.stack(densities)                      # [step, micro, layer]
    return {"losses": losses, "grad_norms": g0, "delta_norms": delta,
            "density": [float(x) for x in dens.mean(axis=(0, 1))],
            "needed_margin": np.stack(needed).tolist()}
