"""The one traffic generator: every mix is a data file under
``perfbench/traffic/`` that this module reads.

Two kinds of file:

``"kind": "requests"`` -- a stream of serving requests.  Every size in it
(prompt part, output length, application, gap between arrivals) comes
from a FIXED set: the ``pool`` evenly spaced quantiles of the stated
distribution, dealt into a FIXED order (``dealt``: every ``block``
consecutive requests hold the whole distribution).
The schedule -- when a request arrives, which application it belongs to,
how long its prompt is and how many tokens it asks for -- is the mix's
own; ``--seed`` draws every token id.  So two seeds offer a window the
same work, token for token of length, and a run-to-run difference is the
system's, not the sample's: a seeded ORDER of the same sizes moved the
tokens a 45 s window completes by +-4% and its mean gap by +-8%
(PERF.md section 2).

``"kind": "batches"`` -- a ring of training batches (``batch`` rows of
``seq_len`` + 1 tokens, or ``batch`` images), made once from the seed.

Nothing here touches JAX: the runner hands the arrays to the program.
"""
from statistics import NormalDist

import numpy as np


def _quantiles(n):
    return (np.arange(n) + 0.5) / n


def lognormal_set(spec, n):
    """``n`` evenly spaced quantiles of lognormal(median, sigma), clipped
    to [min, max], as ints."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    vals = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def zipf_set(count, s, n):
    """``n`` application indices whose shares follow Zipf(s) over
    ``count`` applications (largest-remainder rounding)."""
    w = 1.0 / np.arange(1, count + 1) ** s
    exact = w / w.sum() * n
    take = np.floor(exact).astype(np.int64)
    for i in np.argsort(exact - take)[::-1][:n - take.sum()]:
        take[i] += 1
    return np.repeat(np.arange(count), take)


def exponential_set(rate, n):
    """``n`` evenly spaced quantiles of the exponential gap at ``rate``
    per second; their mean is 1/rate to within 1/n."""
    gaps = -np.log1p(-_quantiles(n)) / rate
    return gaps * (1.0 / rate) / gaps.mean()


def dealt(values, block, rng):
    """``values`` in an order ``rng`` draws, dealt so that every run of
    ``block`` consecutive ones holds one value from each of ``block``
    equal slices of the sorted set: any stretch of the stream then
    offers the whole distribution."""
    values = np.sort(np.asarray(values))
    if values.size % block:
        raise ValueError("pool %d is no multiple of block %d"
                         % (values.size, block))
    hands = np.stack([rng.permutation(s) for s in
                      values.reshape(block, values.size // block)], 1)
    return np.concatenate([rng.permutation(h) for h in hands])


def _aligned_lengths(lo, hi, count, align):
    """``count`` lengths spread evenly over [lo, hi], each a multiple of
    ``align``."""
    pts = np.linspace(lo, hi, count)
    return [int(max(align, round(p / align) * align)) for p in pts]


def starting_outputs(out_set, count):
    """Remaining output lengths of the ``count`` requests in flight at a
    random moment of a steady stream: a request is met in flight in
    proportion to its length (systematic sampling along the summed
    lengths), at an evenly spread point of its progress.  The same set
    for every seed."""
    if not count:
        return np.zeros(0, np.int64)
    lens = np.sort(out_set)
    cum = np.cumsum(lens)
    met = lens[np.searchsorted(cum, _quantiles(count) * cum[-1])]
    left = np.random.default_rng(0).permutation(_quantiles(count))
    return np.maximum(1, np.ceil(met * left)).astype(np.int64)


class RequestStream:
    """Endless iterator of ``(due_s, prompt int32[L], max_new)``.

    ``due_s`` is seconds after the stream's start; a backlog stream is
    all due at 0.  The first ``stagger`` requests are the population a
    steady server would hold at any moment (``starting_outputs``), all
    due at 0, so that the window opens on steady state and not on a
    transient.  ``order`` draws the schedule and is the same for every
    seed; ``rng`` draws the tokens and is the seed's."""

    def __init__(self, mix, seed, vocab, page_size, stagger=0):
        n, block = int(mix["pool"]), int(mix["block"])
        order = np.random.default_rng(0)
        rng = np.random.default_rng([int(seed), 0x7EA])
        self._rng = rng
        self._vocab = int(vocab)
        self._user = dealt(lognormal_set(mix["prompt"], n), block, order)
        self._out = dealt(lognormal_set(mix["output"], n), block, order)
        self._cap = int(mix["prompt_max_total"])
        apps = mix.get("apps")
        if apps:
            lens = _aligned_lengths(apps["system_len"][0],
                                    apps["system_len"][1],
                                    apps["count"], page_size)
            # the popular applications are not the short or the long ones
            lens = [lens[i] for i in order.permutation(len(lens))]
            self._systems = [rng.integers(0, vocab, l).astype(np.int32)
                             for l in lens]
            self._app = dealt(
                zipf_set(apps["count"], apps["zipf_s"], n), block, order)
        else:
            self._systems, self._app = None, None
        arr = mix["arrivals"]
        if arr["process"] == "poisson":
            self._gaps = dealt(
                exponential_set(float(arr["rate_per_s"]), n), block, order)
        elif arr["process"] == "backlog":
            self._gaps = None
        else:
            raise ValueError("unknown arrival process %r" % arr["process"])
        self._start = order.permutation(starting_outputs(
            lognormal_set(mix["output"], n), stagger))
        self._i = 0
        self._t = 0.0

    def __iter__(self):
        return self

    def __next__(self):
        i, k = self._i, self._i % len(self._user)
        user = int(self._user[k])
        parts = []
        if self._systems is not None:
            system = self._systems[int(self._app[k])]
            user = max(1, min(user, self._cap - system.size))
            parts.append(system)
        user = min(user, self._cap)
        parts.append(self._rng.integers(0, self._vocab, user)
                     .astype(np.int32))
        max_new = int(self._start[i]) if i < len(self._start) \
            else int(self._out[k])
        due = self._t
        if self._gaps is not None and i >= len(self._start):
            self._t += float(self._gaps[k])
            due = self._t
        self._i += 1
        return due, np.concatenate(parts), max_new

    def mean_output(self):
        return float(self._out.mean())


def requests(mix, seed, vocab, page_size, stagger=0):
    if mix.get("kind") != "requests":
        raise ValueError("traffic mix is not a request stream: %r"
                         % mix.get("kind"))
    return RequestStream(mix, seed, vocab, page_size, stagger)


def token_batches(mix, seed, vocab):
    """Ring of ``{"x", "y"}`` int32 batches for a language-model job."""
    if mix.get("kind") != "batches" or "seq_len" not in mix:
        raise ValueError("traffic mix holds no token batches")
    rng = np.random.default_rng([int(seed), 0xBA7])
    ring = []
    for _ in range(int(mix["ring"])):
        toks = rng.integers(0, vocab, (int(mix["batch"]),
                                       int(mix["seq_len"]) + 1))
        ring.append({"x": toks[:, :-1].astype(np.int32),
                     "y": toks[:, 1:].astype(np.int32)})
    return ring


def image_batches(mix, seed, image_shape, classes):
    """Ring of ``(data float32[B, C, H, W], label float32[B])``."""
    if mix.get("kind") != "batches" or "seq_len" in mix:
        raise ValueError("traffic mix holds no image batches")
    rng = np.random.default_rng([int(seed), 0x1A6])
    shape = (int(mix["batch"]),) + tuple(image_shape)
    return [(rng.uniform(-1, 1, shape).astype(np.float32),
             rng.integers(0, classes, shape[0]).astype(np.float32))
            for _ in range(int(mix["ring"]))]
