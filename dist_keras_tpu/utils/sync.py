"""Device-queue draining for honest wall-clock timing.

The reference times training with plain wall clocks around the Spark job
(``distkeras/trainers.py:~60``).  Our trainers do the same around the
compiled dispatch — but JAX dispatch and device transfers are
asynchronous: an H2D transfer issued *before* the timed window can
complete *inside* it, charging data distribution to "training", and a
clock stopped before the device finishes measures the enqueue.

``drain`` closes both holes with a jitted last-element probe per shard
plus ONE blocking fetch per device: a fetch is data-dependent, so it
cannot return until the producing transfer or computation has really run
on the device, and per-device in-order execution makes the final probe
cover everything enqueued before it.  Trainers call it

- on the input batches AND carry state after ``_put_worker_chunk`` /
  ``_stack_workers`` and BEFORE ``record_training_start`` — data
  distribution is not training time (the reference's analogue, Spark
  repartitioning, happens before its workers start training too);
- on the output params INSIDE the per-chunk timing window — so the
  recorded seconds cover all compute the chunk actually did.

On a directly attached chip ``jax.block_until_ready`` gives the same
answer (CHANGES.md PR 21 times one chunk both ways); the function stays
because its callers and tests do.
"""

from __future__ import annotations

import jax

_probe = None


def _last_probe():
    """Jitted last-element readback: runs ON the device and fetches 4
    bytes, never the whole buffer."""
    global _probe
    if _probe is None:
        import jax.numpy as jnp

        _probe = jax.jit(
            lambda a: a.ravel()[-1:].astype(jnp.float32).sum())
    return _probe


def drain(*trees):
    """Block until every pending computation/transfer producing the given
    pytrees' leaves has completed on their devices.

    Returns the number of probes dispatched.  Non-device leaves (numpy
    arrays, python scalars) are skipped — they have nothing pending.
    EVERY addressable shard of every leaf is probed (a jitted
    last-element fetch: a streamed transfer completes front-to-back, so
    element 0 can be readable while the tail is still in flight):
    per-device queues are in-order but there is no cross-device ordering,
    so draining only one device's shard would leave the other devices'
    transfers free to complete inside a subsequent timed window.

    All probes are DISPATCHED asynchronously and only the last probe per
    device is fetched: in-order execution per device makes one blocking
    fetch per device cover everything enqueued before it.
    """
    probe = _last_probe()
    last_by_device = {}
    count = 0
    for tree in trees:
        for leaf in jax.tree.leaves(tree):
            if jax.dtypes.issubdtype(getattr(leaf, "dtype", None),
                                     jax.dtypes.prng_key):
                leaf = jax.random.key_data(leaf)  # typed keys: probe raw
            shards = getattr(leaf, "addressable_shards", None)
            if not shards:
                continue
            for shard in shards:
                last_by_device[shard.device] = probe(shard.data)
                count += 1
    for result in last_by_device.values():
        float(result)
    return count
