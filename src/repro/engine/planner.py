"""Backend selection: route each query batch by term statistics.

The planner is deliberately a pure function of cheap observables —
per-term f_t and chain length (both O(1) head-block reads), query batch
size, and index shape (growth policy, word level) — so planning cost never
rivals execution cost.  Routing rules, in priority order:

1. a forced override (``Query.backend`` or ``Engine(force_backend=...)``)
   wins unconditionally and raises if the backend can't run the query;
2. word-level indexes run on the host or tiered backends (the two that
   model word positions); positional modes (phrase / proximity /
   bm25_prox) go to the tiered backend when a static tier is published
   (positions served from the compressed ⟨d,w⟩ image) and to the host
   otherwise; non-Const growth additionally rules out the device image
   (device snapshots need B-addressable blocks) but NOT the Pallas
   kernels, which decode postings host-side;
3. batches of ``device_min_batch`` or more queries go to the device image:
   batched fixed-shape execution amortizes the dispatch and the gather
   touches every query's chains in one fused program.  When the config
   carries a measured :class:`CrossoverTable` (engine_bench.py sweep),
   the threshold is the per-mode batch size at which the device — or the
   fused Pallas kernel — actually beat the host, replacing the static
   guess; a mode where neither ever won is never batch-routed off host;
4. single/small queries whose candidate volume (min f_t for conjunctive —
   the driver of DAAT cost — or Σ f_t for ranked) exceeds
   ``pallas_min_postings`` go to the Pallas kernels;
5. when the lifecycle has published a static tier (``tiered_available``),
   remaining queries whose candidate volume stays under
   ``tiered_max_volume`` go to the tiered backend: the frozen docid prefix
   is served from the compressed image (bp128 skip tables for seek_GEQ)
   and only the post-freeze suffix touches the live chains.  This trades a
   modest per-query decode cost (see BENCH_engine.json: tiered runs
   1.4–2.6× the host latency on hot terms) for keeping the working set in
   the ~1.6 B/posting static image instead of the dynamic chains — the
   volume gate bounds the absolute penalty to the small-query regime where
   it is microseconds;
6. everything else stays on the host, whose seek_GEQ skipping beats a
   device round-trip on short chains.

The Pallas rules (the crossover's pallas column and rule 4) apply only
when the config opts in with ``allow_pallas=True``: the Pallas flavour of
the fused kernel does not compile for a TPU (v5e refuses its rank-1 block
specs, ``jnp.flip``, scatter-add and ``lax.top_k``), so by default nothing
is routed there on any platform, and tests and chips take the same routes.
A query or engine that forces ``backend="pallas"`` still gets it.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import NamedTuple

from .types import POSITIONAL_MODES, Query, TermStats


@dataclass(frozen=True)
class CrossoverTable:
    """Measured device-routing crossovers, derived from benchmark sweeps.

    ``min_batch[mode][backend]`` is the smallest measured batch size at
    which ``backend`` ("device" or "pallas") beat the host's steady-state
    µs/query at EVERY swept collection size (conservative: a backend must
    win across sizes before the planner prefers it), or None when it never
    won.  Built by ``benchmarks/engine_bench.py`` from its workload ×
    collection size × batch size sweep and stored in
    ``BENCH_engine.json["crossover"]`` — :meth:`from_bench` re-derives the
    table from that file, so planner thresholds are measurements, not
    guesses.
    """

    min_batch: dict = field(default_factory=dict)

    @classmethod
    def from_rows(cls, rows) -> "CrossoverTable":
        """Derive the table from sweep rows: dicts with ``workload``,
        ``backend``, ``size``, ``batch``, ``us_per_query`` (steady-state)."""
        cells: dict[tuple, dict[str, float]] = {}
        for r in rows:
            key = (r["workload"], int(r["batch"]), int(r["size"]))
            cells.setdefault(key, {})[r["backend"]] = float(r["us_per_query"])
        modes = sorted({k[0] for k in cells})
        batches = sorted({k[1] for k in cells})
        table: dict[str, dict[str, int | None]] = {}
        for mode in modes:
            table[mode] = {}
            for backend in ("device", "pallas"):
                win = None
                for b in batches:
                    group = [v for k, v in cells.items()
                             if k[0] == mode and k[1] == b]
                    if group and all(backend in v and "host" in v
                                     and v[backend] < v["host"]
                                     for v in group):
                        win = b
                        break
                table[mode][backend] = win
        return cls(min_batch=table)

    @classmethod
    def from_bench(cls, path: str = "BENCH_engine.json") -> "CrossoverTable":
        """Load the sweep rows recorded by ``engine_bench.py`` and re-derive
        the crossover thresholds from them."""
        import json
        with open(path) as fh:
            payload = json.load(fh)
        return cls.from_rows(payload["crossover"]["rows"])

    def min_batch_for(self, mode: str, backend: str) -> int | None:
        """Measured min winning batch for (mode, backend); None = never won
        or mode not swept (caller falls back to static defaults)."""
        per_mode = self.min_batch.get(mode)
        if per_mode is None:
            return None
        return per_mode.get(backend)

    @property
    def swept_modes(self) -> tuple[str, ...]:
        return tuple(self.min_batch)


@dataclass(frozen=True)
class PlannerConfig:
    """Thresholds for the routing rules (see module docstring).

    When ``crossover`` is set (a :class:`CrossoverTable` from
    ``engine_bench.py`` measurements), the batch-size device/pallas rules
    use its per-mode measured thresholds instead of ``device_min_batch``;
    modes the sweep never measured keep the static default, and a mode
    where the accelerated path never beat the host is never batch-routed
    to it.
    """

    device_min_batch: int = 4       # batch size at which the device image wins
    pallas_min_postings: int = 2048  # candidate volume at which kernels win
    tiered_max_volume: int = 2048   # volume ceiling for tiered routing
    allow_device: bool = True
    allow_pallas: bool = False      # opt-in: no TPU build (module doc)
    allow_tiered: bool = True
    crossover: CrossoverTable | None = None  # measured thresholds (bench)


class PlanDecision(NamedTuple):
    backend: str
    reason: str


class Planner:
    def __init__(self, config: PlannerConfig | None = None,
                 force_backend: str | None = None):
        self.config = config or PlannerConfig()
        self.force_backend = force_backend

    def plan(self, query: Query, batch_size: int, stats: list[TermStats],
             *, device_capable: bool, pallas_capable: bool = True,
             tiered_available: bool = False,
             tiered_capable: bool = True) -> PlanDecision:
        """Pick a backend for ``query`` arriving in a batch of ``batch_size``.

        ``stats`` aligns with ``query.terms``; ``device_capable`` reports
        whether the index layout supports device images (Const-mode,
        doc-level), ``pallas_capable`` whether the kernels apply (doc-level
        — Pallas decodes postings host-side, so variable-block growth is
        fine, but word-level lists carry w-gap payloads and duplicate
        docids the kernels do not model).  ``tiered_capable`` reports
        whether the tiered backend can run THIS query (it serves both doc-
        and word-level images; phrase queries need a word-level one);
        ``tiered_available`` whether a static tier is actually published —
        routing prefers it over the host only then, since with no tier it
        degenerates to the host path with extra indirection.
        """
        cfg = self.config
        forced = query.backend or self.force_backend
        if forced is not None:
            unsupported = (
                (query.mode in POSITIONAL_MODES
                 and forced in ("device", "pallas")) or
                (forced == "device" and not device_capable) or
                (forced == "pallas" and not pallas_capable) or
                (forced == "tiered" and not tiered_capable))
            if forced in ("device", "pallas", "tiered") and unsupported:
                raise ValueError(
                    f"backend {forced!r} forced, but {query.mode!r} queries "
                    "on this index layout do not support it")
            return PlanDecision(forced, "forced override")
        if query.mode in POSITIONAL_MODES:
            if cfg.allow_tiered and tiered_capable and tiered_available:
                return PlanDecision(
                    "tiered",
                    f"{query.mode} served from the compressed ⟨d,w⟩ tier")
            return PlanDecision("host",
                                f"{query.mode} requires word positions")
        if cfg.allow_device and device_capable:
            if cfg.crossover is not None \
                    and query.mode in cfg.crossover.swept_modes:
                mb = cfg.crossover.min_batch_for(query.mode, "device")
                if mb is not None and batch_size >= mb:
                    return PlanDecision(
                        "device", f"measured crossover: device wins "
                                  f"{query.mode} at batch >= {mb}")
            elif batch_size >= cfg.device_min_batch:
                return PlanDecision(
                    "device",
                    f"batch of {batch_size} amortizes device dispatch")
        if (cfg.allow_pallas and pallas_capable and device_capable
                and cfg.crossover is not None
                and query.mode in cfg.crossover.swept_modes):
            mb = cfg.crossover.min_batch_for(query.mode, "pallas")
            if mb is not None and batch_size >= mb:
                return PlanDecision(
                    "pallas", f"measured crossover: fused kernel wins "
                              f"{query.mode} at batch >= {mb}")
        fts = [s.ft for s in stats if s.ft > 0]
        if not fts:
            return PlanDecision("host", "no term statistics (empty terms)")
        volume = min(fts) if query.mode == "conjunctive" else sum(fts)
        if (cfg.allow_pallas and pallas_capable
                and volume >= cfg.pallas_min_postings):
            return PlanDecision(
                "pallas", f"candidate volume {volume} favours kernels")
        if (cfg.allow_tiered and tiered_capable and tiered_available
                and volume <= cfg.tiered_max_volume):
            return PlanDecision(
                "tiered", "static tier serves the frozen prefix compressed")
        return PlanDecision(
            "host", f"candidate volume {volume} favours cursor skipping")
