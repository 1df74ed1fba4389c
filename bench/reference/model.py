"""Plain float32 decoder training and AdamW, computed layer by layer.

The part of the plain reference that every model family shares: the
token embedding, a walk over the program's segments of blocks, a final
RMSNorm and an output head, trained with token-mean cross-entropy and
AdamW with global-norm clipping. The blocks themselves are a family's
(`bench/families/<family>.py`, pieces in `bench/reference/decoder.py`):
each is a function `block(x, p, c, quant, q_block)` of the activations
x (B, S, D) f32 and one layer's f32 leaves, returning the new x, or
(x, a scalar loss term) where the program adds one to its loss (an
expert layer's balance and z-losses, with the program's coefficients).
It imports nothing of the program. It takes the weights as a tree in
the program's layout, made by `bench.weights` from the seed, and
computes everything in float32 under "highest" matmul precision.
Where it departs from the published models, it follows the
configuration as run:

  * RMSNorm weights are stored as (weight - 1), so zeros are identity;
  * the output head is its own matrix even where the published model
    ties it to the embedding (the program keeps them apart);
  * the vocabulary is padded to a multiple of 256; padded logits are
    left out of the softmax, so their head columns get no gradient;
  * parameters are stored in the configuration's parameter dtype
    (bfloat16) after each update, as the configuration states.

It runs in pieces so that it fits next to nothing else on one chip:
one layer at a time (activations between layers are kept, each layer's
inside is recomputed in backward), attention in blocks of queries, and
the head and its cross-entropy in blocks of rows. A layer is one block
of one repeat of a segment; layers are counted over all segments in
the program's order.

`quant="fp8"` computes every matmul, forward and backward, from
float8_e4m3 operands with one scale per tensor: the control that a
correct program must beat. `residual_from=(l, k)` plants a spool fault:
layer l's backward gets the residuals (the saved input) of layer k.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                      # largest finite float8_e4m3fn
# queries per attention block and rows per head block: pieces that fit
# beside nothing else on one chip at the benchmark's widths
Q_BLOCK = 1024
HEAD_ROWS = 512


# ------------------------------------------------------------ matmuls

def _q8(x):
    """Round to float8_e4m3 with one scale for the whole tensor."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum_fp8(spec, a, b):
    return jnp.einsum(spec, _q8(a), _q8(b), precision=HIGHEST)


def _einsum_fp8_fwd(spec, a, b):
    return _einsum_fp8(spec, a, b), (a, b)


def _einsum_fp8_bwd(spec, res, g):
    a, b = res
    _, vjp = jax.vjp(
        lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST),
        _q8(a), _q8(b))
    return vjp(_q8(g))


_einsum_fp8.defvjp(_einsum_fp8_fwd, _einsum_fp8_bwd)


def mm(spec: str, a, b, quant: Optional[str]):
    if quant is None:
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    if quant == "fp8":
        return _einsum_fp8(spec, a, b)
    raise ValueError(f"unknown quant {quant!r}")


# ------------------------------------------------------------- blocks

def rms_norm(x, scale, eps: float):
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * r * (1.0 + scale)


def head_nll(x, fnorm, unembed, labels, c, quant):
    """Summed next-token NLL of rows x (R, D) against labels (R,); labels
    below 0 are not counted."""
    h = rms_norm(x, fnorm, c["rms_norm_eps"])
    logits = mm("rd,dv->rv", h, unembed[:, :c["vocab_size"]], quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    safe = jnp.maximum(labels, 0)
    picked = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(labels >= 0, lse - picked, 0.0))


# -------------------------------------------------------------- model

def padded_vocab(c: Dict[str, Any]) -> int:
    return -(-c["vocab_size"] // 256) * 256


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _layer_at(stack, l):
    return _f32(jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, l, keepdims=False),
        stack))


class Reference:
    """Loss, gradients and AdamW steps of the plain model.

    `c` is the configuration (published key names); `blocks` holds, for
    each segment of the program's layout, the block function of each of
    its blocks in the order b0, b1, ...; attention runs in blocks of
    `q_block` queries and the head in blocks of `head_rows` rows (each
    at most the whole)."""

    def __init__(self, c: Dict[str, Any],
                 blocks: Sequence[Sequence[Callable]],
                 quant: Optional[str] = None,
                 residual_from: Optional[Tuple[int, int]] = None,
                 q_block: int = Q_BLOCK, head_rows: int = HEAD_ROWS):
        self.c = dict(c)
        self.quant = quant
        self.residual_from = residual_from
        self.head_rows = head_rows
        quant_ = quant
        cc = self.c

        def jitted(block):
            @jax.jit
            def layer_fwd(x, stack, l):
                return block(x, _layer_at(stack, l), cc, quant_, q_block)

            @jax.jit
            def layer_bwd(x, stack, l, g):
                out, vjp = jax.vjp(
                    lambda x_, p_: block(x_, p_, cc, quant_, q_block),
                    x, _layer_at(stack, l))
                if isinstance(out, tuple):    # d loss / d (x, loss term)
                    return vjp((g, jnp.ones_like(out[1])))
                return vjp(g)

            return layer_fwd, layer_bwd

        @jax.jit
        def embed(table, tokens):
            return table[tokens].astype(jnp.float32)

        @functools.partial(jax.jit, donate_argnums=(5,))
        def head_bwd(x, fnorm, unembed, labels, scale, d_unembed):
            f = lambda x_, n_, u_: head_nll(x_, n_, u_, labels, cc, quant_)
            nll, vjp = jax.vjp(f, x, fnorm.astype(jnp.float32),
                               unembed.astype(jnp.float32))
            dx, dn, du = vjp(scale)
            return nll, dx, dn, d_unembed + du

        self._blocks = [[jitted(b) for b in seg] for seg in blocks]
        self._embed = embed
        self._head_bwd = head_bwd

    def _layers(self, segments) -> List[Tuple[int, int, int]]:
        """(segment, block, repeat) of every layer, in the program's
        order: each repeat of a segment runs its blocks b0, b1, ..."""
        if len(segments) != len(self._blocks) or any(
                set(seg) != {f"b{i}" for i in range(len(fns))}
                for seg, fns in zip(segments, self._blocks)):
            raise ValueError(
                f"the weights' segments {[sorted(s) for s in segments]} "
                f"are not the family's blocks "
                f"{[len(fns) for fns in self._blocks]}")
        out = []
        for s, seg in enumerate(segments):
            n = jax.tree.leaves(seg)[0].shape[0]
            out += [(s, i, r) for r in range(n) for i in range(len(seg))]
        return out

    def loss_and_grads(self, params, batch):
        """(mean loss, f32 gradients in the params' layout). The loss is
        the token-mean cross-entropy plus the blocks' loss terms."""
        segments = params["segments"]
        layers = self._layers(segments)
        tokens = jnp.asarray(batch["tokens"])
        labels = np.asarray(batch["labels"])
        count = max(int(np.sum(labels >= 0)), 1)
        xs = [self._embed(params["embed"], tokens)]
        terms = 0.0
        for s, i, r in layers:
            out = self._blocks[s][i][0](xs[-1], segments[s][f"b{i}"], r)
            if isinstance(out, tuple):
                out, term = out
                terms += float(term)
            xs.append(out)
        B, S, D = xs[-1].shape
        xf = xs[-1].reshape(B * S, D)
        lf = jnp.asarray(labels.reshape(B * S))
        rb = min(self.head_rows, B * S)
        nll = 0.0
        dx_parts = []
        d_norm = None
        d_unembed = jnp.zeros(params["unembed"].shape, jnp.float32)
        scale = jnp.float32(1.0 / count)
        for r in range(0, B * S, rb):
            n, dx, dn, d_unembed = self._head_bwd(
                xf[r:r + rb], params["final_norm"]["scale"],
                params["unembed"], lf[r:r + rb], scale, d_unembed)
            nll += float(n)
            dx_parts.append(dx)
            d_norm = dn if d_norm is None else d_norm + dn
        del xf
        g = jnp.concatenate(dx_parts).reshape(B, S, D)
        del dx_parts
        stacks = [{k: jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                                   v) for k, v in seg.items()}
                  for seg in segments]
        saved = list(range(len(layers)))
        if self.residual_from is not None:
            saved[self.residual_from[0]] = self.residual_from[1]
        for n in reversed(range(len(layers))):
            s, i, r = layers[n]
            b = f"b{i}"
            g, dl = self._blocks[s][i][1](xs[saved[n]], segments[s][b], r, g)
            stacks[s][b] = _set_layer(stacks[s][b], dl, r)
        del xs
        d_embed = _embed_grad(params["embed"].shape, tokens, g)
        grads = {"embed": d_embed, "final_norm": {"scale": d_norm},
                 "segments": stacks, "unembed": d_unembed}
        if jax.tree.structure(grads) != jax.tree.structure(params):
            raise ValueError("the reference handles an embedding, "
                             "segments of stacked blocks, a final norm "
                             "and a head; the weights hold "
                             f"{jax.tree.structure(params)}")
        return nll / count + terms, grads


@functools.partial(jax.jit, donate_argnums=(0,))
def _set_layer(stack, layer_grads, l):
    return jax.tree.map(lambda a, b: a.at[l].set(b), stack, layer_grads)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed_grad(shape, tokens, g):
    return jnp.zeros(shape, jnp.float32).at[tokens].add(g)


# ---------------------------------------------------------------- adamw

@jax.jit
def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(tree)))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2),
                   static_argnames=("lr", "b1", "b2", "eps", "wd"))
def _adam_leaf(p, m, v, g, scale, step, *, lr, b1, b2, eps, wd):
    g = g * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mhat = m / (1 - b1 ** step)
    vhat = v / (1 - b2 ** step)
    u = mhat / (jnp.sqrt(vhat) + eps) + wd * p.astype(jnp.float32)
    return (p.astype(jnp.float32) - lr * u).astype(p.dtype), m, v


def adamw_step(params, mu, nu, grads, step: int, opt: Dict[str, float]):
    """One AdamW update with global-norm clipping, leaf by leaf; `grads`
    is consumed. Returns (params, mu, nu, unclipped gradient norm,
    clip scale)."""
    gn = float(global_norm(grads))
    clip = opt.get("clip_norm")
    scale = min(1.0, clip / max(gn, 1e-9)) if clip else 1.0
    hyper = dict(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                 wd=opt["weight_decay"])
    pl, treedef = jax.tree.flatten(params)
    ml, vl, gl = (jax.tree.leaves(t) for t in (mu, nu, grads))
    del grads
    out_p, out_m, out_v = [], [], []
    for i in range(len(pl)):
        g = gl[i]
        gl[i] = None
        p, m, v = _adam_leaf(pl[i], ml[i], vl[i], g, jnp.float32(scale),
                             jnp.float32(step), **hyper)
        out_p.append(p)
        out_m.append(m)
        out_v.append(v)
    un = lambda l: jax.tree.unflatten(treedef, l)  # noqa: E731
    return un(out_p), un(out_m), un(out_v), gn, scale


def zeros_f32(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def train_three(ref: Reference, params, batches: Sequence[Dict],
                opt: Dict[str, float], leaf_norms):
    """Follow the program's first len(batches) steps from `params`.

    Returns the readings the comparison needs: the loss of each step,
    the leaf norms of the first step's gradient as the optimizer gets it
    (after clipping), its unclipped global norm, and the parameters after
    the last step. `leaf_norms` maps a tree to its named leaf norms."""
    mu = zeros_f32(params)
    nu = zeros_f32(params)
    losses = []
    grad_norms = None
    gn0 = None
    for t, batch in enumerate(batches):
        loss, grads = ref.loss_and_grads(params, batch)
        losses.append(loss)
        if t == 0:
            gn0 = float(global_norm(grads))
            clip = opt.get("clip_norm")
            s = min(1.0, clip / max(gn0, 1e-9)) if clip else 1.0
            grad_norms = {k: v * s for k, v in leaf_norms(grads).items()}
        params, mu, nu, _, _ = adamw_step(params, mu, nu, grads, t + 1, opt)
        del grads
    del mu, nu
    return {"losses": losses, "grad_leaf_norms": grad_norms,
            "grad_norm": gn0, "params": params}
