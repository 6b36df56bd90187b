"""Fan-out scan executor with a crash-safe incremental trial store.

One trial = build a small index at a concrete :class:`~repro.tuner.space.
TrialSpec` point and measure, through the REAL ``repro.api`` /
``repro.engine`` query path (never a simulation):

  * ``recall``     — held-out recall@k against the exact oracle
  * ``cand_frac``  — mean unique candidates / n (the sublinearity metric)
  * ``cost``       — the planner's deterministic candidate+slot cost model
                     (the latency axis of the Pareto table; wall-clock-free
                     so resumed and fresh scans agree bit-for-bit)
  * ``mem_bytes``  — bytes of the built index state
  * ``us_per_query`` — measured wall time (advisory only: recorded for
                     humans, EXCLUDED from the frontier so the tuning-table
                     artifact stays bit-reproducible)

Execution fans out across worker PROCESSES (``workers=N`` spawns fresh
interpreters — each gets its own jax runtime, so a crashed or OOM-killed
trial never takes the scan down) and optionally across devices: trials with
``shards > 1`` build through ``Index.shard`` and measure the sharded query
path (skipped with a recorded reason when the host has too few devices).

Crash safety is the JSONL trial store: one fsync'd line per COMPLETED
trial, keyed by the content-addressed ``trial_id``. Resuming a partial run
re-enumerates the space, skips every stored id, tolerates a torn trailing
line (the crash artifact), and rejects a store written for a different
space. Per-trial seeds derive from the trial ids, so the completed grid —
and the Pareto frontier built from it — is bit-identical no matter how many
times the scan died on the way there.
"""

from __future__ import annotations

import json
import os
import time

from repro.tuner.space import (
    AUTO_WIDTH,
    ScanSpace,
    TrialSpec,
    profile_data,
    profile_queries,
    profile_weights,
)

__all__ = [
    "TrialStore",
    "WorkersOnChipError",
    "run_trial",
    "run_scan",
    "resolve_width",
    "scan_is_complete",
    "trial_cost",
]


class WorkersOnChipError(RuntimeError):
    """``workers > 1`` on a TPU backend: a TPU belongs to one process at a
    time, so spawned workers would fail or hang waiting for the chip the
    parent already holds. Run trials inline (``workers=0``) there."""


# relative cost of a probed (table, probe, slot) vs one reranked candidate —
# mirrors Planner.slot_cost so scan costs and plan costs rank identically
SLOT_COST = 0.02


def trial_cost(
    L: int,
    n_probes: int,
    window: int,
    mean_cand: float,
    mean_tables: float | None = None,
) -> float:
    """The deterministic latency proxy used for Pareto dominance.

    ``mean_tables`` is the measured mean probe windows visited (early-exit
    trials): the slot term then charges only the expected fraction of the
    L·n_probes lattice the streamed tail actually touched — the
    expected-tables-probed cost column dominance runs over. None (or a
    full sweep) charges the whole lattice, exactly the pre-streaming
    model."""
    slots = float(L * n_probes * window)
    if mean_tables is not None:
        slots *= min(1.0, float(mean_tables) / float(L * n_probes))
    return float(mean_cand) + SLOT_COST * slots


def resolve_width(trial: TrialSpec, data, key) -> float:
    """Resolve ``W="auto"`` for an l2 trial: anchor the bucket width at the
    planner's collision-prob goal on the 75th percentile of the transformed
    kth-NN near distance — the same scale-robust rule
    ``Planner._solve_family`` applies, computed on the trial's own data."""
    import jax
    import jax.numpy as jnp

    from repro.api.planner import Planner
    from repro.core import theory, transforms
    from repro.core.transforms import BoundedSpace
    from repro.kernels import ops

    space = BoundedSpace(0.0, 1.0, float(trial.M))
    m = min(trial.queries, trial.profile.n)
    k_rows, k_j, k_w = jax.random.split(key, 3)
    rows = jax.random.choice(k_rows, data.shape[0], (m,), replace=False)
    qs = data[rows] + jax.random.uniform(
        k_j, (m, trial.profile.d), minval=-1 / space.t, maxval=1 / space.t
    )
    ws = profile_weights(k_w, (m, trial.profile.d), trial.profile.skew)
    levels = transforms.discretize(data, space).astype(jnp.float32)
    qlevels = transforms.discretize(qs, space).astype(jnp.float32)
    kk = min(trial.k + 1, data.shape[0])
    nn_d, _ = ops.wl1_scan_topk(levels, qlevels, ws, kk)
    r1 = jnp.maximum(nn_d[:, kk - 1], 1e-6)
    s1 = theory.l2_distance_from_wl1(r1, max(space.M, 1), trial.profile.d, ws)
    c_star = 1.0 / theory.invert_p_l2(Planner._P1_GOAL, 1.0)
    return float(c_star * jnp.quantile(s1, 0.75))


def run_trial(trial_dict: dict, real_data=None) -> dict:
    """Execute one trial; returns the store record (a plain JSON dict).

    Deterministic given the trial content (except the advisory
    ``us_per_query`` wall-clock field). Importable at module top level so
    spawn-based worker pools can pickle it.
    """
    import jax
    import jax.numpy as jnp

    from repro.api import Index, IndexConfig, PlannedSpec, QuerySpec
    from repro.core.transforms import BoundedSpace
    from repro.distance import recall_at_k
    from repro.launch.mesh import make_mesh

    trial = TrialSpec.from_dict(trial_dict)
    rec = {"trial_id": trial.trial_id, "trial": trial.to_dict(), "status": "ok"}
    if trial.shards > 1 and jax.device_count() < trial.shards:
        rec.update(
            status="skipped",
            reason=f"needs {trial.shards} devices, host has {jax.device_count()}",
        )
        return rec

    key = jax.random.PRNGKey(trial.seed)
    data = profile_data(trial.profile, jax.random.fold_in(key, 0), real_data)
    W = trial.W
    if W == AUTO_WIDTH:
        W = (
            resolve_width(trial, data, jax.random.fold_in(key, 1))
            if trial.family == "l2"
            else 4.0
        )
    cfg = IndexConfig(
        d=trial.profile.d, M=trial.M, K=trial.K, L=trial.L,
        family=trial.family, W=float(W), max_candidates=trial.window,
        space=BoundedSpace(0.0, 1.0, float(trial.M)),
    )
    index = Index.build(jax.random.fold_in(key, 2), data, cfg)

    qs = profile_queries(
        trial.profile, jax.random.fold_in(key, 3), trial.queries, real_data
    )
    ws = profile_weights(
        jax.random.fold_in(key, 4), (trial.queries, trial.profile.d),
        trial.profile.skew,
    )
    spec = PlannedSpec(
        k=trial.k, mode="multiprobe" if trial.n_probes > 1 else "probe",
        n_probes=trial.n_probes if trial.n_probes > 1 else 1,
        max_flips=trial.max_flips, max_candidates=trial.window,
        early_exit=trial.early_exit, exit_group=trial.exit_group,
        exit_slack=trial.exit_slack,
    )
    handle = index
    if trial.shards > 1:
        handle = index.shard(make_mesh((trial.shards,), ("data",)))

    res = handle.query(qs, ws, spec)
    exact = handle.query(qs, ws, QuerySpec(k=trial.k, mode="exact"))
    recall = float(recall_at_k(res.ids, exact.ids, trial.k))
    mean_cand = float(jnp.mean(res.n_candidates))
    mean_tables = (
        float(jnp.mean(res.tables_probed))
        if res.tables_probed is not None
        else None
    )

    # advisory wall time: median of 3 warm calls (compile excluded)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(handle.query(qs, ws, spec).ids)
        times.append(time.perf_counter() - t0)
    times.sort()

    rec.update(
        family=trial.family, K=trial.K, L=trial.L, W=float(W),
        n_probes=trial.n_probes, max_flips=trial.max_flips,
        window=trial.window, k=trial.k, shards=trial.shards,
        early_exit=trial.early_exit, exit_group=trial.exit_group,
        exit_slack=trial.exit_slack,
        tables_probed=mean_tables,
        recall=recall,
        cand_frac=mean_cand / trial.profile.n,
        cost=trial_cost(
            trial.L, trial.n_probes, trial.window, mean_cand, mean_tables
        ),
        mem_bytes=int(
            sum(x.nbytes for x in jax.tree_util.tree_leaves(index.state))
        ),
        us_per_query=times[1] / trial.queries * 1e6,
    )
    return rec


def _pool_trial(args) -> dict:
    trial_dict, real = args
    return run_trial(trial_dict, real_data=real)


class TrialStore:
    """Append-only JSONL store of completed trial records.

    Line 0 is a header naming the :class:`ScanSpace` content hash; every
    following line is one completed trial. Writes are flushed + fsync'd per
    record, so a kill between trials loses nothing and a kill mid-write
    leaves at most one torn TRAILING line, which ``load`` tolerates. A torn
    or alien line anywhere else means the store is corrupt (or belongs to a
    different scan) and raises a named error instead of silently merging.
    """

    def __init__(self, path: str | os.PathLike):
        self.path = os.fspath(path)

    def exists(self) -> bool:
        return os.path.exists(self.path)

    def has_data(self) -> bool:
        return self.exists() and os.path.getsize(self.path) > 0

    def repair(self) -> None:
        """Truncate a torn TRAILING line (the mid-write crash artifact).
        Run before resuming appends: left in place, the torn line would sit
        ABOVE the resumed records and read as interior corruption on the
        next load."""
        if not self.exists():
            return
        with open(self.path, "rb") as f:
            raw = f.read()
        lines = raw.split(b"\n")
        while lines and not lines[-1].strip():
            lines.pop()
        if not lines:
            return
        try:
            json.loads(lines[-1])
            return  # intact store, nothing to do
        except json.JSONDecodeError:
            pass
        keep = b"\n".join(lines[:-1])
        with open(self.path, "wb") as f:
            if keep:
                f.write(keep + b"\n")
            f.flush()
            os.fsync(f.fileno())

    def write_header(self, space: ScanSpace) -> None:
        os.makedirs(os.path.dirname(os.path.abspath(self.path)), exist_ok=True)
        with open(self.path, "w") as f:
            f.write(json.dumps(
                {"kind": "space", "space_id": space.space_id}, sort_keys=True
            ) + "\n")
            f.flush()
            os.fsync(f.fileno())

    def load(self, space: ScanSpace | None = None) -> dict:
        """Parse the store into ``{trial_id: record}`` (first write wins —
        duplicate ids cannot disagree, they are content-addressed). Checks
        the header against ``space`` when given."""
        records: dict = {}
        if not self.exists():
            return records
        with open(self.path) as f:
            lines = f.read().splitlines()
        for i, line in enumerate(lines):
            if not line.strip():
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                if i == len(lines) - 1:
                    continue  # torn trailing line from a mid-write crash
                raise ValueError(
                    f"{self.path}:{i + 1} is not valid JSON (and is not the "
                    f"trailing line) — the trial store is corrupt; delete it "
                    f"to rescan from scratch"
                ) from None
            if i == 0:
                if rec.get("kind") != "space":
                    raise ValueError(
                        f"{self.path} has no space header — not a tuner "
                        f"trial store"
                    )
                if space is not None and rec.get("space_id") != space.space_id:
                    raise ValueError(
                        f"{self.path} was written for scan space "
                        f"{rec.get('space_id')!r} but this scan is "
                        f"{space.space_id!r} — point the scan at a fresh "
                        f"store (mixing spaces would corrupt the frontier)"
                    )
                continue
            records.setdefault(rec["trial_id"], rec)
        return records

    def append(self, record: dict) -> None:
        with open(self.path, "a") as f:
            f.write(json.dumps(record, sort_keys=True) + "\n")
            f.flush()
            os.fsync(f.fileno())


def run_scan(
    space: ScanSpace,
    store_path: str | os.PathLike,
    workers: int = 0,
    real_data=None,
    max_trials: int | None = None,
    log=None,
) -> list:
    """Run (or resume) the scan; returns completed records in canonical
    trial order.

    Args:
      space: the declarative grid to cover.
      store_path: JSONL trial store — created with a space header if absent,
        resumed (completed ids skipped) if present.
      workers: 0/1 runs trials inline; N > 1 fans out over N spawned worker
        processes (each with its own jax runtime) — CPU backends only:
        on a TPU it raises :class:`WorkersOnChipError`.
      real_data: (rows, d) array backing ``source="sampled"`` profiles.
      max_trials: stop after this many NEW completions (crash/resume drills
        and budgeted incremental scans); None runs the grid dry.
      log: optional ``print``-like progress callback.
    """
    if workers > 1:
        import jax

        if jax.default_backend() == "tpu":
            raise WorkersOnChipError(
                f"run_scan(workers={workers}) on a TPU backend: the chip "
                f"belongs to one process, so spawned workers cannot use it "
                f"— run with workers=0 (trials inline in this process)"
            )
    trials = space.trials()
    store = TrialStore(store_path)
    store.repair()  # drop a torn trailing line before appending below it
    done = store.load(space)
    unknown = set(done) - {t.trial_id for t in trials}
    if unknown:
        raise ValueError(
            f"{store.path} holds {len(unknown)} trial(s) not in this scan "
            f"space (e.g. {sorted(unknown)[:3]}) despite a matching header — "
            f"the store is corrupt; delete it to rescan"
        )
    if not store.has_data():
        store.write_header(space)
    pending = [t for t in trials if t.trial_id not in done]
    if max_trials is not None:
        pending = pending[: max(0, max_trials)]
    if log:
        log(
            f"scan {space.space_id}: {len(trials)} trials total, "
            f"{len(done)} stored, {len(pending)} to run "
            f"(workers={workers})"
        )

    if pending:
        real = None
        if real_data is not None:
            import numpy as np

            real = np.asarray(real_data)
        if workers <= 1:
            for t in pending:
                rec = run_trial(t.to_dict(), real_data=real)
                done[rec["trial_id"]] = rec
                store.append(rec)
                if log:
                    log(f"  trial {rec['trial_id']} {rec['status']}")
        else:
            import multiprocessing as mp

            ctx = mp.get_context("spawn")  # fresh interpreters: jax-safe
            with ctx.Pool(processes=workers) as pool:
                jobs = [(t.to_dict(), real) for t in pending]
                for rec in pool.imap_unordered(_pool_trial, jobs):
                    done[rec["trial_id"]] = rec
                    store.append(rec)
                    if log:
                        log(f"  trial {rec['trial_id']} {rec['status']}")
    return [done[t.trial_id] for t in trials if t.trial_id in done]


def scan_is_complete(space: ScanSpace, store_path: str | os.PathLike) -> bool:
    """True when every trial of ``space`` has a stored record."""
    done = TrialStore(store_path).load(space)
    return all(t.trial_id in done for t in space.trials())
