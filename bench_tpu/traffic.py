"""The benchmark's one traffic generator: data and requests from a seed.

Everything a run sends to the store is drawn here from ``--seed``, the
configuration file (key universe and preload) and the traffic file (the
mix). A traffic file holds parameters only; this module reads them all:

closed loop (one caller; the next call waits for the previous one)::

    {"loop": "closed", "op": "insert" | "lookup" | "range",
     "batch": 100000,                      # records, keys or windows a call
     "keys": {"from": "universe" | "preloaded",
              "dist": "uniform" | "zipf", "theta": 0.99},
     "absent_share": 0.5,                  # lookups: share of key|1 probes
     "scan_records": [1, 100],             # ranges: records a window spans
     "warm_calls": 3, "readback_max": 262144}

open loop (independent clients; Poisson arrivals at a fixed rate)::

    {"loop": "open", "rate_per_s": 400, "warm_s": 3,
     "mix": {"read": 0.5, "update": 0.5},  # single-key reads and updates
     "keys": {"from": "preloaded", "dist": "zipf" | "uniform",
              "theta": 0.99},
     "readback_max": 262144}

Keys are int32. The universe is `KeySpace`: evenly spaced even keys, so
``key | 1`` is a key that was never written. Zipf ranks map to keys
through a seeded permutation, so hot keys are spread over the key space.
"""
from __future__ import annotations

import numpy as np

INT32_MAX = np.iinfo(np.int32).max

# independent random streams of one seed
PRELOAD, TRAFFIC, WARM, CHECK = range(4)


def stream(seed: int, *purpose: int) -> np.random.Generator:
    """The random stream of `seed` for one purpose (and call index)."""
    return np.random.default_rng([seed % (1 << 63), *purpose])


def zipf_probs(universe: int, theta: float) -> np.ndarray:
    """Exact rank probabilities p_i proportional to 1/i^theta, bounded."""
    w = 1.0 / np.power(np.arange(1, universe + 1, dtype=np.float64), theta)
    return w / w.sum()


class Zipf:
    """Bounded Zipf ranks 0..n-1 by inverse CDF (rank 0 hottest)."""

    def __init__(self, n: int, theta: float):
        self.cdf = np.cumsum(zipf_probs(n, theta))

    def sample(self, rng: np.random.Generator, m: int) -> np.ndarray:
        r = np.searchsorted(self.cdf, rng.random(m) * self.cdf[-1],
                            side="right")
        return np.minimum(r, self.cdf.size - 1)


class KeySpace:
    """`size` even keys spread evenly over the int32 range."""

    def __init__(self, size: int):
        self.size = int(size)
        self.step = 2 * ((INT32_MAX - 1) // (2 * self.size))
        if self.step < 2:
            raise ValueError(f"key universe {size} does not fit int32")

    def keys_at(self, idx) -> np.ndarray:
        return (np.asarray(idx, np.int64) * self.step).astype(np.int32)

    def index(self, keys):
        """(position, inside) of each key in the universe."""
        k = np.asarray(keys, np.int64)
        q, r = np.divmod(k, self.step)
        inside = (k >= 0) & (r == 0) & (q < self.size)
        return np.where(inside, q, 0), inside

    def index_bounds(self, lo: int, hi: int) -> tuple[int, int]:
        """Positions [a, b) of the universe's keys in [lo, hi)."""
        ceil = lambda x: -(-int(x) // self.step)
        return (min(max(ceil(lo), 0), self.size),
                min(max(ceil(hi), 0), self.size))


def random_vals(rng: np.random.Generator, n: int) -> np.ndarray:
    return rng.integers(-2**31, 2**31, n, dtype=np.int64).astype(np.int32)


class Data:
    """One seed's data set: the preload, and the keys traffic draws from.

    The preload inserts `preload_keys` distinct keys of the universe in
    calls of `preload_call` records, then overwrites `preload_overwrites`
    of them and deletes `preload_deletes` others, so the newest version
    of a key may sit in a younger structure than its first."""

    def __init__(self, config: dict, seed: int):
        self.seed = seed
        self.space = KeySpace(config["key_universe"])
        rng = stream(seed, PRELOAD)
        n = int(config["preload_keys"])
        self.keys = self.space.keys_at(       # in insertion order
            rng.choice(self.space.size, n, replace=False))
        vals = random_vals(rng, n)
        n_ow = int(config["preload_overwrites"])
        n_del = int(config["preload_deletes"])
        pick = rng.choice(n, n_ow + n_del, replace=False)
        ow, dl = self.keys[pick[:n_ow]], self.keys[pick[n_ow:]]
        step = int(config["preload_call"])
        self.calls = [("insert", self.keys[o:o + step], vals[o:o + step])
                      for o in range(0, n, step)]
        self.calls.append(("insert", ow, random_vals(rng, n_ow)))
        self.calls.append(("delete", dl, np.zeros(n_del, np.int32)))
        # mean key distance between preloaded keys: turns a window's
        # length in records into a key span
        self.gap = self.space.step * self.space.size / n
        self._zipf: dict[float, Zipf] = {}
        self._perm = None

    @property
    def n_records(self) -> int:
        return sum(k.size for _, k, _ in self.calls)

    def zipf(self, theta: float) -> Zipf:
        if theta not in self._zipf:
            self._zipf[theta] = Zipf(self.keys.size, theta)
        return self._zipf[theta]

    def hot_order(self) -> np.ndarray:
        """Preloaded keys in a seeded order: Zipf rank r is key [r]."""
        if self._perm is None:
            self._perm = stream(self.seed, TRAFFIC).permutation(self.keys)
        return self._perm

    def draw(self, rng: np.random.Generator, spec: dict, m: int
             ) -> np.ndarray:
        """`m` keys by a traffic file's ``keys`` spec."""
        if spec.get("from", "preloaded") == "universe":
            if spec.get("dist", "uniform") != "uniform":
                raise ValueError("keys from the universe are uniform")
            return self.space.keys_at(rng.integers(0, self.space.size, m))
        dist = spec.get("dist", "uniform")
        if dist == "uniform":
            return self.keys[rng.integers(0, self.keys.size, m)]
        if dist == "zipf":
            ranks = self.zipf(float(spec["theta"])).sample(rng, m)
            return self.hot_order()[ranks]
        raise ValueError(f"unknown key distribution {dist!r}")

    def span(self, rng: np.random.Generator, spec: list, m: int
             ) -> np.ndarray:
        """Key spans of `m` windows of ``spec = [lo, hi]`` records."""
        recs = rng.integers(int(spec[0]), int(spec[1]) + 1, m)
        return np.maximum(1, np.round(recs * self.gap)).astype(np.int64)


def windows(starts: np.ndarray, spans: np.ndarray) -> np.ndarray:
    """(m, 2) int32 [lo, hi) windows, clipped below the reserved key."""
    lo = np.asarray(starts, np.int64)
    hi = np.minimum(lo + spans, INT32_MAX - 1)
    return np.stack([lo, hi], axis=1).astype(np.int32)


def closed_batch(data: Data, traffic: dict, rng: np.random.Generator):
    """One call's arguments for a closed-loop traffic file: insert ->
    (keys, vals); lookup -> keys; range -> (m, 2) windows."""
    op, b = traffic["op"], int(traffic["batch"])
    if op == "insert":
        return data.draw(rng, traffic["keys"], b), random_vals(rng, b)
    if op == "lookup":
        n_abs = int(round(b * float(traffic.get("absent_share", 0.0))))
        ks = data.draw(rng, traffic["keys"], b)
        ks[:n_abs] |= 1
        return rng.permutation(ks)
    if op == "range":
        return windows(data.draw(rng, traffic["keys"], b),
                       data.span(rng, traffic["scan_records"], b))
    raise ValueError(f"unknown closed-loop op {op!r}")


class Requests:
    """A fixed list of open-loop requests: due times (seconds from the
    start) and, per request, its kind, key and value."""

    KINDS = ("read", "update")

    def __init__(self, due, kind, key, val):
        self.due, self.kind, self.key, self.val = due, kind, key, val

    def __len__(self) -> int:
        return self.due.size


def open_requests(data: Data, traffic: dict, seconds: float,
                  rng: np.random.Generator) -> Requests:
    """round(rate * seconds) requests at Poisson arrival times in
    [0, seconds) (uniform order statistics: the count is fixed, so every
    seed offers the same work), drawn from the traffic file's mix."""
    n = int(round(float(traffic["rate_per_s"]) * seconds))
    due = np.sort(rng.random(n)) * seconds
    names = list(traffic["mix"])
    unknown = set(names) - set(Requests.KINDS)
    if unknown:
        raise ValueError(f"unknown request kinds {sorted(unknown)}")
    p = np.asarray([traffic["mix"][k] for k in names], np.float64)
    kind = np.asarray([Requests.KINDS.index(k) for k in names])[
        rng.choice(len(names), n, p=p / p.sum())]
    key = data.draw(rng, traffic["keys"], n)
    return Requests(due, kind, key, random_vals(rng, n))
