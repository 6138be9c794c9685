"""Plain float32 reference of one DCCO training round, written from the
paper (arXiv:2210.00092 Sec 3, Eq. 1-3, Fig. 2; Appendix A) and
independent of ``src/``. What does not depend on the encoder lives here:
the key schedule, the CCO loss on the five statistics, Adam and the
blocked round driver. A configuration's own reference module (its
``"reference"`` path, e.g. ``bench/reference.py``) gives the encoder,
the views and the initialisation:

    init_params(key, model) -> params in the program's pytree layout
    encode(model, params, batch, dt) -> (B, d) projections; ``dt`` is the
        dtype of every product's operands (None: float32), each operand
        passed through ``lowp``
    views(key, sample) -> (view1, view2), what ``encode`` takes, of one
        sample given as a dict of its leaves in their sample shapes

One round: the server samples K clients (``jax.random.choice`` without
replacement), each sample gets two views, the encoder encodes both, the
CCO loss is taken on the five statistics aggregated over every valid
sample of the cohort (Eq. 3), and Adam steps on the averaged client
delta. With one local step of plain GD at client lr ``eta``, Appendix A
makes the averaged delta ``-eta * grad`` of that aggregated loss: every
client steps on the same stop-grad combine, whose value is the
aggregate. So the reference takes one gradient of the centralized loss
over the union of the cohort's valid samples, in blocks of samples so
that it fits.

Every product runs at ``Precision.HIGHEST`` (float32 on a TPU). The key
schedule mirrors the round engine's (``fold_in(rng, round)``, then split
into selection and augmentation keys) so that the reference sees the same
cohort and the same views.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


# -- lower-precision products (the control) ------------------------------------

def _round_scaled(x, dtype):
    """x rounded to ``dtype`` with one scale per tensor (amax to the
    format's largest finite value), returned in float32."""
    top = float(jnp.finfo(dtype).max)
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top)
    return (x / s).astype(dtype).astype(F32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def lowp(x, dtype):
    """A product operand computed in ``dtype`` (None: float32): rounded in
    the forward pass, and its cotangent rounded in the backward pass,
    each with its own per-tensor scale, as scaled low-precision training
    does."""
    return x if dtype is None else _round_scaled(x, dtype)


def _lowp_fwd(x, dtype):
    return lowp(x, dtype), None


def _lowp_bwd(dtype, _, g):
    return (g if dtype is None else _round_scaled(g, dtype),)


lowp.defvjp(_lowp_fwd, _lowp_bwd)


# -- CCO loss (Eq. 1-2) on the five statistics (Eq. 3) ---------------------------

def stat_sums(encode, model, params, v1, v2, mask, dt=None):
    """Unnormalized sums of the five statistics over the valid rows."""
    zf = encode(model, params, v1, dt)
    zg = encode(model, params, v2, dt)
    m = mask[:, None]
    return {"f": (m * zf).sum(0), "ff": (m * zf * zf).sum(0),
            "g": (m * zg).sum(0), "gg": (m * zg * zg).sum(0),
            "fg": jnp.dot(lowp((m * zf).T, dt), lowp(zg, dt), precision=HI)}


def cco_loss(st, lam):
    """Paper Eq. 1 with the 1/(d-1) off-diagonal weight; variances floored
    at 1e-6 (1 + second moment), which no healthy statistic reaches."""
    var_f = jnp.maximum(st["ff"] - st["f"] ** 2, 1e-6 * (1 + jnp.abs(st["ff"])))
    var_g = jnp.maximum(st["gg"] - st["g"] ** 2, 1e-6 * (1 + jnp.abs(st["gg"])))
    cov = st["fg"] - jnp.outer(st["f"], st["g"])
    c = cov / (jnp.sqrt(var_f + 1e-8)[:, None] * jnp.sqrt(var_g + 1e-8)[None, :])
    d = c.shape[0]
    diag = jnp.diagonal(c)
    return (jnp.sum((1.0 - diag) ** 2)
            + lam * (jnp.sum(c * c) - jnp.sum(diag * diag)) / (d - 1))


# -- the cohort ------------------------------------------------------------------

def round_keys(rng, r):
    k_sel, k_aug = jax.random.split(jax.random.fold_in(rng, r))
    return k_sel, k_aug


def cohort(k_sel, num_clients, k_round):
    return jax.random.choice(k_sel, num_clients, (k_round,), replace=False)


# -- Adam with the cosine schedule ---------------------------------------------------

def adam_init(params):
    z = jax.tree.map(jnp.zeros_like, params)
    return {"step": jnp.zeros((), jnp.int32), "m": z, "v": z}


def adam_step(server, params, state, grad):
    b1, b2, eps = server["b1"], server["b2"], server["eps"]
    t = jnp.clip(state["step"].astype(F32) / server["cosine_rounds"], 0.0, 1.0)
    lr = server["lr"] * 0.5 * (1.0 + jnp.cos(jnp.pi * t))
    step = state["step"] + 1
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, state["m"], grad)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, state["v"], grad)
    bc1 = 1 - b1 ** step.astype(F32)
    bc2 = 1 - b2 ** step.astype(F32)
    params = jax.tree.map(
        lambda p, m_, v_: p - lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps),
        params, m, v)
    return params, {"step": step, "m": m, "v": v}


# -- rounds ------------------------------------------------------------------------

class Reference:
    """Follows ``rounds`` rounds of a cell from the seed's parameters.

    ``family`` is the configuration's reference module (see above);
    ``traffic`` holds the host arrays the benchmark generated; ``block``
    is the number of samples per view encoded in one call."""

    def __init__(self, family, model, traffic, cell, block: int = 256,
                 operand_dtype=None):
        self.model, self.traffic, self.cell = model, traffic, cell
        self.block = block
        dt = self.dt = operand_dtype
        self.lam = float(model["lambda_cco"])
        server = dict(cell["server"])
        self.server = server
        m = model
        shapes = traffic.shapes
        self._sel = jax.jit(lambda k: cohort(k, traffic.num_clients,
                                             cell["clients_per_round"]))
        self._views = jax.jit(jax.vmap(lambda k, x: family.views(
            k, {name: x[name].reshape(shapes[name]) for name in shapes})))
        sums = functools.partial(stat_sums, family.encode, m, dt=dt)
        self._sums = jax.jit(sums)

        def loss_cot(sums, n):
            st = jax.tree.map(lambda s: s / n, sums)
            loss, cot = jax.value_and_grad(cco_loss)(st, self.lam)
            return loss, jax.tree.map(lambda c: c / n, cot)

        self._loss_cot = jax.jit(loss_cot)

        def block_grad(p, v1, v2, mask, cot):
            _, vjp = jax.vjp(lambda q: sums(q, v1, v2, mask), p)
            return vjp(cot)[0]

        self._block_grad = jax.jit(block_grad)
        self._adam = jax.jit(functools.partial(adam_step, server))
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
        if cell["channel"] != "none":
            raise ValueError(f"no reference for channel {cell['channel']!r}")

    def round_inputs(self, rng, r):
        """The round's valid samples: views (padded to whole blocks) and
        their mask."""
        tr, k = self.traffic, self.cell["clients_per_round"]
        k_sel, k_aug = round_keys(rng, r)
        sel = np.asarray(self._sel(k_sel))
        sizes = tr.client_sizes[sel]
        n_pad = tr.client_index.shape[1]
        slot_k, slot_j = np.nonzero(np.arange(n_pad)[None, :] < sizes[:, None])
        n_valid = len(slot_k)
        total = -(-n_valid // self.block) * self.block
        keys = jax.random.split(k_aug, k * n_pad)
        flat = slot_k * n_pad + slot_j
        rows = tr.client_index[sel[slot_k], slot_j]
        pad = total - n_valid
        flat = np.concatenate([flat, np.repeat(flat[:1], pad)])
        rows = np.concatenate([rows, np.repeat(rows[:1], pad)])
        mask = np.concatenate([np.ones(n_valid, np.float32),
                               np.zeros(pad, np.float32)])
        v1, v2 = self._views(keys[flat], {
            name: jnp.asarray(v[rows]) for name, v in tr.leaves.items()})
        return v1, v2, jnp.asarray(mask), n_valid

    def step(self, params, opt, rng, r):
        """One round; returns (params, opt, loss, gradient)."""
        v1, v2, mask, n_valid = self.round_inputs(rng, r)
        b = self.block
        blocks = [jax.tree.map(lambda x: x[i:i + b], (v1, v2, mask))
                  for i in range(0, mask.shape[0], b)]
        sums = None
        for blk in blocks:
            s = self._sums(params, *blk)
            sums = s if sums is None else self._add(sums, s)
        loss, cot = self._loss_cot(sums, jnp.asarray(n_valid, F32))
        grad = None
        for blk in blocks:
            g = self._block_grad(params, *blk, cot)
            grad = g if grad is None else self._add(grad, g)
        # one local GD step at client lr eta: the averaged delta is
        # -eta * grad, and the server's pseudo-gradient is its negative
        grad = jax.tree.map(lambda g: self.cell["client_lr"] * g, grad)
        params, opt = self._adam(params, opt, grad)
        return params, opt, float(loss), grad

    def follow(self, params, rng, rounds: int):
        """Run ``rounds`` rounds from ``params``; returns the final
        params and Adam state, each round's loss and the first round's
        gradient."""
        opt = adam_init(params)
        losses, first_grad = [], None
        for r in range(rounds):
            params, opt, loss, grad = self.step(params, opt, rng, r)
            losses.append(loss)
            if r == 0:
                first_grad = grad
        return params, opt, losses, first_grad
