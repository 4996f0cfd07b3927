"""Model registration: DNN layer graph -> (latency, bandwidth, energy) tables.

This is the paper's "registration phase" (Sec. 3): every DNN model that may
be requested is characterized offline on every sub-accelerator, producing
the ``c[i, s, m]`` / ``b[i, s, m]`` tables the online scheduler consumes.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.costmodel.accelerators import MASConfig, layer_cost
from repro.costmodel.layers import LayerSpec


@dataclasses.dataclass(frozen=True)
class ModelTable:
    """Characterization of one DNN model on one MAS."""
    name: str
    layers: tuple[LayerSpec, ...]
    latency_us: np.ndarray     # (L, M) float64
    bw_gbps: np.ndarray        # (L, M)
    energy_uj: np.ndarray      # (L, M)
    deps: np.ndarray           # (L,) int32: predecessor layer idx or -1
    # first row of the pass a job re-enters for each further output
    # token (an LM request's decode pass); None: one pass, the chain
    decode_start: int | None = None

    @property
    def num_layers(self) -> int:
        return len(self.layers)

    @property
    def min_pass_latency_us(self) -> tuple[float, float]:
        """Contention-free latency of the first pass (up to
        ``decode_start``: an LM request's prefill, its time to first
        token) and of one re-entered pass (a decode step)."""
        best = self.latency_us.min(axis=1)
        ds = self.num_layers if self.decode_start is None \
            else self.decode_start
        return float(best[:ds].sum()), float(best[ds:].sum())

    @property
    def min_latency_us(self) -> float:
        """Contention-free lower bound: best SA per layer, chain-sequential.

        This is the PREMA-style "isolated execution latency" used to derive
        SLA targets: q_j = qos_factor * min_latency.
        """
        return float(self.latency_us.min(axis=1).sum())

    @property
    def min_energy_uj(self) -> float:
        return float(self.energy_uj.min(axis=1).sum())


def register_model(name: str, layers: list[LayerSpec], mas: MASConfig,
                   deps: list[int] | None = None,
                   decode_start: int | None = None) -> ModelTable:
    L, M = len(layers), mas.num_sas
    lat = np.zeros((L, M))
    bw = np.zeros((L, M))
    en = np.zeros((L, M))
    for li, layer in enumerate(layers):
        for mi, sa in enumerate(mas.sas):
            lat[li, mi], bw[li, mi], en[li, mi] = layer_cost(
                sa, layer, dram_gbps=mas.dram_gbps)
    if deps is None:
        deps = [-1] + list(range(L - 1))  # linear chain
    if decode_start is not None and not 0 < decode_start < L:
        raise ValueError(f"{name}: decode_start {decode_start} outside "
                         f"the chain of {L} layers")
    return ModelTable(name=name, layers=tuple(layers), latency_us=lat,
                      bw_gbps=bw, energy_uj=en,
                      deps=np.asarray(deps, dtype=np.int32),
                      decode_start=decode_start)


class Registry:
    """All registered models of a deployment, with dense padded tables.

    Produces the fixed-shape arrays the JAX environment indexes into:
      lat/bw/en: (num_models, Lmax, M) padded with zeros
      n_layers:  (num_models,)
      deps:      (num_models, Lmax)
      min_lat:   (num_models,)

    and, where a model re-enters (:attr:`reenters`), ``decode_start``
    (``n_layers`` for a one-pass model), ``min_first`` and ``min_pass``
    (:attr:`ModelTable.min_pass_latency_us`), each ``(num_models,)``.
    """

    def __init__(self, mas: MASConfig):
        self.mas = mas
        self.tables: dict[str, ModelTable] = {}
        self._order: list[str] = []

    def register(self, name: str, layers: list[LayerSpec],
                 deps: list[int] | None = None,
                 decode_start: int | None = None) -> ModelTable:
        tab = register_model(name, layers, self.mas, deps, decode_start)
        self.tables[name] = tab
        self._order.append(name)
        return tab

    @property
    def model_names(self) -> list[str]:
        return list(self._order)

    @property
    def reenters(self) -> bool:
        """Whether a model's jobs re-enter the queue per output token."""
        return any(t.decode_start is not None for t in self.tables.values())

    def model_id(self, name: str) -> int:
        return self._order.index(name)

    def dense(self) -> dict[str, np.ndarray]:
        n = len(self._order)
        lmax = max(t.num_layers for t in self.tables.values())
        M = self.mas.num_sas
        lat = np.zeros((n, lmax, M), np.float64)
        bw = np.zeros((n, lmax, M), np.float64)
        en = np.zeros((n, lmax, M), np.float64)
        deps = np.full((n, lmax), -1, np.int32)
        nl = np.zeros((n,), np.int32)
        minlat = np.zeros((n,), np.float64)
        for i, name in enumerate(self._order):
            t = self.tables[name]
            L = t.num_layers
            lat[i, :L] = t.latency_us
            bw[i, :L] = t.bw_gbps
            en[i, :L] = t.energy_uj
            deps[i, :L] = t.deps
            nl[i] = L
            minlat[i] = t.min_latency_us
        out = dict(lat=lat, bw=bw, en=en, deps=deps, n_layers=nl,
                   min_lat=minlat, lmax=lmax, num_models=n, num_sas=M)
        if self.reenters:
            tabs = [self.tables[name] for name in self._order]
            out["decode_start"] = np.asarray(
                [t.num_layers if t.decode_start is None else t.decode_start
                 for t in tabs], np.int32)
            split = np.asarray([t.min_pass_latency_us for t in tabs])
            out["min_first"], out["min_pass"] = split[:, 0], split[:, 1]
        return out
