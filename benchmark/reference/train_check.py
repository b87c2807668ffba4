"""The comparison that decides ``correct`` for a training cell.

The program's first three steps (the window's own call and feed) against
the plain float32 reference following the same three steps from the same
seeded weights and batches: every step's loss; the first gradient as the
optimizer got it, recovered from Adam's first moment after one step
(mu_1 = (1 - b1) g_1), by norm and by a seeded sample of 256 entries of
every leaf; the norm of the parameters' change after the three steps.
Norms are compared leaf by leaf, as the gap between the two norms against
the reference's norm of that leaf or of the median leaf, whichever is
larger (some gradients are all but zero); the worst leaf is the number.
A norm hides rounding (zero-mean errors move it in second order only), so
the sampled entries are compared entry by entry, pooled over all leaves:
that is the number a lower precision moves.
"""

from __future__ import annotations

import numpy as np

from benchmark import checks, trafficgen, weights
from benchmark.reference import transformer_ref as ref

B1 = 0.9
SAMPLE = 256


def _leaf_norms(tree):
    import jax
    import jax.numpy as jnp

    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def _leaf_samples(tree, index):
    import jax
    import jax.numpy as jnp

    return jnp.stack([x.reshape(-1)[i].astype(jnp.float32)
                      for x, i in zip(jax.tree.leaves(tree), index)])


class Probes:
    """Small readings of a state's leaves, each one jitted call."""

    def __init__(self, params, seed, cfg):
        import jax

        rng = np.random.default_rng([int(seed), 3])
        index = [rng.integers(0, int(np.prod(x.shape)), SAMPLE)
                 for x in jax.tree.leaves(params)]
        self.names = [jax.tree_util.keystr(p) for p, _ in
                      jax.tree_util.tree_flatten_with_path(params)[0]]
        self._of = jax.jit(
            lambda t: (_leaf_norms(t), _leaf_samples(t, index)))
        self._change = jax.jit(lambda p, k: _leaf_norms(jax.tree.map(
            lambda a, b: a - b, p, weights.transformer(k, cfg))))

    def of(self, tree):
        """Per-leaf norms and the seeded sample of entries of a tree shaped
        like the parameters."""
        norms, samples = self._of(tree)
        return np.asarray(norms), np.asarray(samples)

    def first_gradient(self, opt_state):
        """Norms and samples of g_1 from optax's Adam state after one
        step."""
        norms, samples = self.of(opt_state[0].mu)
        return norms / (1 - B1), samples / (1 - B1)

    def change(self, params, key):
        """Per-leaf norm of ``params`` minus the seeded initial weights,
        which are made again inside the call and not kept."""
        return np.asarray(self._change(params, key))


def reference_steps(cfg, traffic, train, key, steps, probes,
                    prec=ref.FLOAT32):
    """``steps`` steps of plain Adam on the seeded weights and feed, in
    ``prec`` -> dict of losses, first-gradient norms and samples, and the
    change's norms."""
    import jax
    import jax.numpy as jnp

    params = jax.jit(lambda k: jax.tree.map(
        lambda w: w.astype(prec.state), weights.transformer(k, cfg)))(key)
    xs, ys = jax.jit(lambda k: trafficgen.train_batches(
        traffic, train["input_dim"], train["n_classes"], k))(key)
    grad_fn = jax.jit(lambda p, x, y: ref.batch_loss_and_grad(p, x, y, prec))
    update = jax.jit(ref.adam_update, static_argnames=("step", "lr"),
                     donate_argnums=(0, 2, 3))
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    out = {"losses": []}
    for s in range(steps):
        loss, grad = grad_fn(params, xs[s % xs.shape[0]],
                             ys[s % ys.shape[0]])
        out["losses"].append(float(loss))
        if s == 0:
            out["grad_norms"], out["grad_samples"] = probes.of(grad)
        params, mu, nu = update(params, grad, mu, nu, step=s + 1,
                                lr=float(train["learning_rate"]))
        del grad
    out["delta_norms"] = probes.change(params, key)
    return out


def gaps(observed, reference):
    """The numbers compared -> (dict of numbers, dict of the leaf index
    at which each worst-leaf number was read)."""
    def worst_norm_gap(a, b):
        gap = np.abs(a - b) / np.maximum(b, np.median(b))
        return float(np.max(gap)), int(np.argmax(gap))

    sa, sb = observed["grad_samples"], reference["grad_samples"]
    grad, grad_at = worst_norm_gap(observed["grad_norms"],
                                   reference["grad_norms"])
    delta, delta_at = worst_norm_gap(observed["delta_norms"],
                                     reference["delta_norms"])
    numbers = {
        "loss_gap": float(np.max(np.abs(
            np.asarray(observed["losses"]) - reference["losses"]))),
        "grad_norm_gap": grad,
        "grad_sample_gap": float(np.linalg.norm(sa - sb)
                                 / np.linalg.norm(sb)),
        "delta_norm_gap": delta,
    }
    return numbers, {"grad_norm_gap": grad_at, "delta_norm_gap": delta_at}


def compare(ctx, cfg, first_losses, grad_probe, delta_probe, probes, steps):
    """The program's first steps against the reference -> checks."""
    reference = reference_steps(
        cfg, ctx.traffic, ctx.config["train"], weights.base_key(ctx.seed),
        steps, probes)
    observed = {"losses": first_losses, "grad_norms": grad_probe[0],
                "grad_samples": grad_probe[1], "delta_norms": delta_probe}
    numbers, where = gaps(observed, reference)
    bounds = checks.limits_for(ctx.cell["name"])
    out = [checks.limit(name, numbers[name], bounds[name])
           for name in numbers]
    for check in out:
        if check["name"] in where:
            check["worst_leaf"] = probes.names[where[check["name"]]]
    return out
