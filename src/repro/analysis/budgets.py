"""Declared trace-contract budgets for the jaxpr auditor.

The auditor (:mod:`repro.analysis.audit`) traces the public query
entry-point lattice at the AUDIT geometry below and checks three budgets:

  * **retrace budget** — the compile-key cardinality of the whole lattice
    after :func:`repro.engine.pipeline.normalize_static_args`. The audit
    enumerates RAW caller combinations (including the redundant axes the
    facades and ladder rungs might pass — probe-mode ``n_probes``,
    non-probe ``impl``, f32 ``screen_alpha``) and asserts the normalization
    folds them back to exactly ``RETRACE_BUDGET`` distinct compiled
    programs. A new static axis that the normalization does not fold is a
    budget breach at review time instead of compile stalls in production.
  * **memory envelope** — the peak live intermediate bytes of any single
    traced path (liveness-scanned over the jaxpr, sub-jaxprs included)
    must stay under ``MEMORY_ENVELOPE_BYTES``. The envelope is sized so
    every legitimate HEAD path fits with ~4x headroom while a
    ``(b, L·P·C, cap)``-class dense-delta-match materialization (the
    pre-PR5 regression this gate exists for: 8·4096·4096 f32 ≈ 512 MiB at
    audit geometry) breaches it by an order of magnitude.
  * **dtype contract** — no f64 avals anywhere (silent promotion doubles
    every table and intermediate), and int8 avals may only flow through
    movement/decode primitives (``INT8_ALLOWED_PRIMITIVES``) — int8
    arithmetic outside the gather-tail decode means a kernel is
    accumulating in the quantized domain.

Per-path measurements are additionally diffed against the checked-in
golden file (``golden_budget.json``, regenerate with
``python -m repro.analysis --write-golden``) with ``GOLDEN_REL_TOL``
slack, so a slow creep toward the envelope is visible in review long
before it breaches.
"""

from __future__ import annotations

from pathlib import Path

# The standard audit geometry: small enough that the four index builds the
# auditor needs take ~a second, big enough that the asymptotic shapes
# (candidate blocks, delta-match chunks, screen survivors) are the real
# ones. ``cap`` mirrors the 4096-row delta memory envelope from DESIGN §4.
AUDIT_GEOMETRY = {
    "n": 4096,
    "d": 16,
    "M": 32,
    "K": 4,
    "L": 8,
    "W": 4.0,
    "max_candidates": 64,
    "delta_capacity": 4096,
    "b": 8,  # query batch rows per trace
    "k": 10,
}

# Distinct compiled programs the full audited lattice may cost (exact —
# the lattice is deterministic, so any drift is a real new/removed
# program). Measured on HEAD: 146 raw caller combinations fold to 64 —
# the 14 keys beyond the pre-streaming 50 are the genuine early-exit
# programs (probe+stream per build × view, multiprobe+stream per theta
# storage × view); every other early-exit knob combination must fold.
RETRACE_BUDGET = 64

# Peak live intermediate bytes per traced path. Worst legitimate HEAD path
# is the segmented exact scan at ~18.3 MiB peak (the tombstoned
# two-segment ExhaustiveSource materializes the full id block); 32 MiB
# leaves it headroom while the (b, L·P·C, cap) dense-match regression
# (~512 MiB at audit geometry) breaches by 16x.
MEMORY_ENVELOPE_BYTES = 32 * 2**20

# Relative tolerance for the per-path golden diff (jax version skew moves
# fusion/liveness details a little; real regressions move them a lot).
GOLDEN_REL_TOL = 0.10

GOLDEN_PATH = Path(__file__).with_name("golden_budget.json")

# Primitives int8 avals may legitimately flow through: the quantized table
# is MOVED (gathered, sliced, reshaped, scanned through) and DECODED
# (convert_element_type) — never computed on. Anything else consuming an
# int8 operand is quantized-domain arithmetic outside the decode tail.
INT8_ALLOWED_PRIMITIVES = frozenset(
    {
        "convert_element_type",  # the decode itself (widen to f32)
        "gather",
        "dynamic_slice",
        "dynamic_update_slice",
        "slice",
        "squeeze",
        "reshape",
        "broadcast_in_dim",
        "concatenate",
        "transpose",
        "rev",
        "select_n",  # two-segment owner select moves encoded rows
        "pad",
        "copy",
        # structural plumbing that forwards operands untouched
        "jit",
        "scan",
        "while",
        "cond",
        "custom_jvp_call",
        "custom_vjp_call",
        "stop_gradient",
    }
)
