"""Worker step times of a simulated cluster: the benchmark's copy of the
program's ``cluster.simulator`` generators (``ClusterSim``, its presets and
the row-shared partition of ``PartitionedSim``), with the same draws for
the same seed, kept here so that a change to the program cannot change the
traffic.

Regimes as the paper observes them (Fig. 2): workers share nodes, a slow
node persists for tens of iterations, contention periods, and heavy-tailed
per-worker spikes."""
from __future__ import annotations

import numpy as np

# Settings per preset: the paper's 158-worker cluster (4 nodes x 40 cores,
# 1 PS + 1 spare; mean 1.057 s, std 0.393 s) and the Cray XC40's 2175
# workers, as the program defines them.
PRESETS = {
    "cluster_sim": {},
    "paper_cluster_158": dict(n_nodes=4, base_mean=1.0, worker_hetero=0.15,
                              noise_sigma=0.07, spike_prob=0.02,
                              spike_scale=0.9),
    "cray_xc40_2175": dict(n_nodes=32, base_mean=1.0, worker_hetero=0.1,
                           noise_sigma=0.05, spike_prob=0.01,
                           spike_scale=0.7, regime_stay=0.99),
}


class ClusterTimes:
    """Regime-switching, node-correlated step-time generator."""

    def __init__(self, n_workers: int, seed: int, *, n_nodes: int = 4,
                 base_mean: float = 1.0, worker_hetero: float = 0.15,
                 noise_sigma: float = 0.07, ar_rho: float = 0.9,
                 ar_sigma: float = 0.05, spike_prob: float = 0.015,
                 spike_scale: float = 0.8, regime_stay: float = 0.985):
        self.n_workers, self.n_nodes = n_workers, n_nodes
        self.noise_sigma, self.ar_rho, self.ar_sigma = (noise_sigma, ar_rho,
                                                        ar_sigma)
        self.spike_prob, self.spike_scale = spike_prob, spike_scale
        self.regime_stay = regime_stay
        rng = self._rng = np.random.default_rng(seed)
        sizes = np.full(n_nodes, n_workers // n_nodes)
        sizes[: n_workers % n_nodes] += 1
        self.node_of = np.repeat(np.arange(n_nodes), sizes)
        self.mu = base_mean * (1.0 + worker_hetero
                               * (rng.uniform(size=n_workers) - 0.3))
        ones = np.ones(n_nodes)
        self.regimes = [(ones, 0.0)]
        for k in range(n_nodes):
            m = ones.copy()
            m[k] = 1.9
            self.regimes.append((m, 0.0))
        self.regimes.append((ones * 1.35, 0.12))
        self._state = rng.integers(len(self.regimes))
        self._load = np.zeros(n_nodes)

    @classmethod
    def preset(cls, name: str, n_workers: int, seed: int) -> "ClusterTimes":
        return cls(n_workers, seed, **PRESETS[name])

    def step(self) -> np.ndarray:
        """One iteration's joint step times, (n_workers,) seconds."""
        rng = self._rng
        if rng.uniform() > self.regime_stay:
            self._state = rng.integers(len(self.regimes))
        node_mult, extra = self.regimes[self._state]
        self._load = (self.ar_rho * self._load
                      + self.ar_sigma * rng.standard_normal(self.n_nodes))
        node_factor = node_mult * np.exp(self._load)
        sigma = self.noise_sigma + extra
        noise = np.exp(sigma * rng.standard_normal(self.n_workers)
                       - 0.5 * sigma ** 2)
        spikes = np.where(rng.uniform(size=self.n_workers) < self.spike_prob,
                          1.0 + rng.exponential(self.spike_scale,
                                                self.n_workers), 1.0)
        return self.mu * node_factor[self.node_of] * noise * spikes

    def run(self, n_steps: int) -> np.ndarray:
        return np.stack([self.step() for _ in range(n_steps)])


class Partitioned:
    """J jobs on contiguous, near-equal slices of one cluster's workers.

    Row ``i`` is drawn once for the whole cluster and shared, so every
    job's times come from the same joint draw (node regimes belong to the
    hardware, not to a job).  ``times(job, i)`` is job ``job``'s slice of
    row ``i``; rows every job has read are dropped."""

    def __init__(self, base: ClusterTimes, n_jobs: int):
        sizes = np.full(n_jobs, base.n_workers // n_jobs)
        sizes[: base.n_workers % n_jobs] += 1
        bounds = np.concatenate([[0], np.cumsum(sizes)])
        self.base = base
        self.slices = [slice(bounds[j], bounds[j + 1])
                       for j in range(n_jobs)]
        self._rows: dict = {}
        self._drawn = 0
        self._reads = np.zeros(n_jobs, int)

    def times(self, job: int, i: int) -> np.ndarray:
        while self._drawn <= i:
            self._rows[self._drawn] = self.base.step()
            self._drawn += 1
        out = self._rows[i][self.slices[job]]
        self._reads[job] = max(self._reads[job], i + 1)
        for k in [k for k in self._rows if k < self._reads.min()]:
            del self._rows[k]
        return out
