"""One general traffic generator, driven by a data file of parameters.

Every seed gets the SAME multiset of sizes and arrival gaps, in another
order: lengths and gaps are the distribution's quantiles at evenly spaced
probabilities (a stratified draw), and only the permutation and the token
values come from the seed.  So two seeds offer the same work, and a run's
numbers differ by the system's noise and not by the luck of the draw.

A mix is a list of ``classes`` (share, prompt lengths, output lengths);
``max_total`` cuts a reply to the positions its prompt leaves of a slot.
Arrivals are ``poisson`` at ``rate_per_s``, optionally in ``burst``
groups that arrive together at the same mean rate; ``in_flight_at_start``
further requests are due at the window's first instant, so that the
window samples a server that has been running and not one that fills up
from empty.  A closed loop has
``clients`` and no arrival process; its ``block`` deals the requests so
that every stretch of the list holds the same mix.  With ``stagger_start``
each caller's FIRST request has its reply cut so that the callers' phases
are spread evenly over a cycle from the start (``closed_order``).
"""

from __future__ import annotations

import itertools
import math
from statistics import NormalDist

import numpy as np


def quantile(dist, u):
    """The ``u``-quantile (0 < u < 1) of a length distribution -> int."""
    kind = dist["dist"]
    if kind == "uniform":
        x = dist["min"] + (dist["max"] - dist["min"]) * u
    elif kind == "lognormal":
        x = math.exp(math.log(dist["median"])
                     + dist["sigma"] * NormalDist().inv_cdf(u))
    elif kind == "exponential":
        # of all distributions of a positive length with this mean the one
        # that assumes nothing else: for a source that publishes the mean
        x = -dist["mean"] * math.log(1.0 - u)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    x = int(round(x))
    return max(dist.get("min", x), min(dist.get("max", x), x))


def stratified(dist, n):
    """``n`` lengths: the quantiles at (i + 1/2) / n, in rising order."""
    return [quantile(dist, (i + 0.5) / n) for i in range(n)]


def arrival_gaps(arrival, n):
    """``n`` gaps (seconds) between arrival instants, in rising order."""
    rate = float(arrival["rate_per_s"]) / int(arrival.get("burst", 1))
    if arrival["process"] == "poisson":
        return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    raise ValueError(f"unknown arrival process {arrival['process']!r}")


def requests(traffic, n, vocab, seed):
    """``n`` requests -> list of dicts (``prompt`` int32 array,
    ``max_new``, ``cls``), class by class in the mix's shares, shuffled
    by the seed."""
    rng = np.random.default_rng([int(seed), 1])
    classes = traffic["classes"]
    shares = [c.get("share", 1.0) for c in classes]
    counts = [int(round(n * s / sum(shares))) for s in shares]
    counts[0] += n - sum(counts)
    out = []
    for ci, (cls, k) in enumerate(zip(classes, counts)):
        prompts = stratified(cls["prompt_len"], k)
        outputs = stratified(cls["output_len"], k)
        rng.shuffle(outputs)
        room = int(traffic.get("max_total", 0))
        for p, o in zip(prompts, outputs):
            if room:
                o = max(1, min(o, room - p))
            out.append({"cls": ci, "max_new": int(o),
                        "prompt": rng.integers(0, vocab, p, dtype=np.int32)})
    block = int(traffic.get("block", 1))
    if block > 1:
        return _blocked(out, block, rng)
    order = rng.permutation(len(out))
    return [out[i] for i in order]


def _blocked(reqs, block, rng):
    """Order requests so that every run of ``block`` consecutive ones holds
    one from each of ``block`` strata of output length: whichever stretch
    of the list a window reaches, it does the same mix of work (a plain
    shuffle let the mean reply length of a sub-window, and with it the
    share of time spent in prefill, swing by the seed: 1.2% of a rate)."""
    reqs = sorted(reqs, key=lambda r: r["max_new"])
    per = len(reqs) // block
    strata = [list(rng.permutation(per) + s * per) for s in range(block)]
    out = []
    for b in range(per):
        members = [reqs[strata[s][b]] for s in range(block)]
        out.extend(members[i] for i in rng.permutation(block))
    return out + [reqs[i] for i in rng.permutation(
        range(per * block, len(reqs)))]


def closed_order(traffic, pool, clients):
    """The requests of a closed loop in the order they are sent, without
    end: the pool, cycled.  With ``stagger_start`` caller ``i`` of
    ``clients`` has the reply of its first request cut to ``ceil(length *
    (i + 1) / clients)`` (marked ``staggered``: a cut reply is not one the
    mix draws): the callers then end their first replies one after another
    at even distances, as those of a server that has run for an hour do,
    and not all at once as callers that began together.  Nothing after a
    caller's first request changes."""
    stagger = clients if traffic.get("stagger_start") else 0
    for i in range(stagger):
        whole = pool[i % len(pool)]
        yield dict(whole, staggered=True,
                   max_new=-(-whole["max_new"] * (i + 1) // clients))
    for at in itertools.count(stagger):
        yield pool[at % len(pool)]


def open_schedule(traffic, seconds, vocab, seed):
    """Requests due inside a window of ``seconds`` -> list of dicts with
    ``due`` (seconds from the window's start), in order of arrival."""
    arrival = traffic["arrival"]
    burst = int(arrival.get("burst", 1))
    groups = max(1, int(round(float(arrival["rate_per_s"]) * seconds
                              / burst)))
    rng = np.random.default_rng([int(seed), 2])
    gaps = np.asarray(arrival_gaps(arrival, groups))
    rng.shuffle(gaps)
    # every seed has the same gaps, so the same sum: scaled to the window
    # exactly, the first arrival is at 0 and the last gap runs to its end
    gaps *= seconds / gaps.sum()
    due = np.cumsum(gaps) - gaps
    ahead = int(traffic.get("in_flight_at_start", 0))
    reqs = requests(traffic, ahead + groups * burst, vocab, seed)
    for i, r in enumerate(reqs):
        r["due"] = 0.0 if i < ahead else float(due[(i - ahead) // burst])
    return reqs


def describe(reqs):
    """The drawn length distributions, for the run's log."""
    def summary(xs):
        xs = sorted(xs)
        return {"n": len(xs), "min": xs[0], "p50": xs[len(xs) // 2],
                "max": xs[-1], "sum": int(sum(xs))}
    return {"prompt_len": summary([len(r["prompt"]) for r in reqs]),
            "output_len": summary([r["max_new"] for r in reqs])}


def train_batches(traffic, input_dim, n_classes, key):
    """A pool of ``batches`` batches from the run's base key (the same
    call gives the program and the reference the same feed), made on the
    device: rows that all differ, cycled through by the window.  A
    batch's rows share one class, and the classes take turns from batch to
    batch: with seeded noise for inputs every row's pooled features are
    nearly alike, so a batch of mixed classes makes the gradient a sum
    that all but cancels, and how nearly is the seed's luck (PR 23 read a
    tenfold swing of the rounding error across seeds).  Row r's inputs
    are scaled by ``row_scales[r]``, so that the rows weigh differently
    in the loss and a step that trains on a part of the batch shows."""
    import jax
    import jax.numpy as jnp

    k = int(traffic["batches"])
    kx, ky = jax.random.split(jax.random.fold_in(key, 0x7FFF))
    x = jax.random.normal(
        kx, (k, traffic["batch"], traffic["seq_len"], input_dim),
        jnp.float32)
    x = x * jnp.asarray(traffic["row_scales"],
                        jnp.float32)[None, :, None, None]
    first = jax.random.randint(ky, (), 0, n_classes)
    y = jnp.broadcast_to(((first + jnp.arange(k)) % n_classes)[:, None],
                         (k, traffic["batch"]))
    return x, y
