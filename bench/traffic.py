"""The one generator of rebalance requests, driven by a mix's data file.

A simulation controller sends one request at a time and waits for the
placement (a closed loop with one client).  A mix (``traffic/<name>.json``)
sets where each rebalance starts and which machine speeds it balances
for:

* ``"start": "uniform"`` — a fresh uniform random placement, as after a
  cold start or a full re-partition;
* ``"start": "previous"`` — the placement the last rebalance returned;
* ``"slow_factor": f`` — before each request the machine slowed by the
  previous event gets its base speed back and another machine, drawn
  from the seed, drops to ``f`` of its base speed (co-tenant or
  throttling churn, the factor of the program's DES ``slowdown``
  scenario).  Without it every request uses the base speeds.

Every draw comes from ``--seed``: request ``i`` of a seed is the same in
every run, however many requests the window completes.  Requests of the
warm-up come from a stream of their own.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
KNOWN_KEYS = {"start", "slow_factor", "why"}


@dataclasses.dataclass(frozen=True)
class Mix:
    start: str
    slow_factor: float | None

    @classmethod
    def load(cls, name: str) -> "Mix":
        params = json.loads((TRAFFIC_DIR / f"{name}.json").read_text())
        unknown = set(params) - KNOWN_KEYS
        if unknown:
            raise ValueError(f"traffic {name}: unknown keys {sorted(unknown)}")
        if params["start"] not in ("uniform", "previous"):
            raise ValueError(f"traffic {name}: start {params['start']!r}")
        return cls(start=params["start"],
                   slow_factor=params.get("slow_factor"))


@dataclasses.dataclass
class Request:
    start: object           # (N,) int32 device array
    speeds: np.ndarray      # (K,) speeds as the program gets them (f32, sum 1)
    key: object             # PRNG key for the entry's coins


def seed_words(seed: int) -> tuple[int, int]:
    """Two 31-bit words from a seed of any size (JAX keys take 32 bits)."""
    w = np.random.SeedSequence(seed).generate_state(2)
    return int(w[0] >> 1), int(w[1] >> 1)


def normalised(speeds) -> np.ndarray:
    s = np.asarray(speeds, np.float64)
    return (s / s.sum()).astype(np.float32)


class Stream:
    """Requests of one mix on one instance, from one seed.

    ``stream`` separates the warm-up's requests (1) from the window's (0).
    """

    def __init__(self, mix: Mix, seed: int, num_nodes: int,
                 base_speeds: np.ndarray, stream: int = 0):
        import jax

        self.mix = mix
        self.n = num_nodes
        self.base = np.asarray(base_speeds, np.float64)
        words = seed_words(seed)
        root = jax.random.fold_in(jax.random.PRNGKey(words[0]), stream)
        self._start_key, self._coin_key = jax.random.split(root)
        self._rng = np.random.default_rng([words[1], stream])
        self._slowed: int | None = None
        self._index = 0
        k = self.base.size
        self._draw = jax.jit(
            lambda key, i: jax.random.randint(
                jax.random.fold_in(key, i), (num_nodes,), 0, k,
                dtype=jax.numpy.int32))

    def uniform_start(self, i: int):
        return self._draw(self._start_key, i)

    def cold(self) -> Request:
        """A uniform start under the base speeds: the warm-up's request,
        and for a mix that starts from the last placement, the cold solve
        its first request starts from."""
        import jax

        return Request(start=self.uniform_start(0),
                       speeds=normalised(self.base),
                       key=jax.random.fold_in(self._coin_key, 0))

    def next(self, previous=None) -> Request:
        import jax

        i = self._index
        self._index += 1
        if self.mix.start == "uniform" or previous is None:
            start = self.uniform_start(i)
        else:
            start = previous
        speeds = self.base.copy()
        if self.mix.slow_factor is not None:
            choices = [m for m in range(self.base.size) if m != self._slowed]
            self._slowed = int(self._rng.choice(choices))
            speeds[self._slowed] *= self.mix.slow_factor
        return Request(start=start, speeds=normalised(speeds),
                       key=jax.random.fold_in(self._coin_key, i))
