"""The program's own trace (repro.obs): host spans in Index.query, the
collector's span, and the engine's stage scopes in the compiled programs."""

import gc
import glob

import jax
import pytest

from repro import obs
from repro.api import BoundedSpace, Index, IndexConfig, QuerySpec
from repro.engine import pipeline

D = 10
STATICS = ("cfg", "k", "mode", "n_probes", "max_flips", "impl", "screen_alpha",
           "early_exit", "exit_group", "exit_slack")


def _index(rng, storage="f32", n=640):
    cfg = IndexConfig(d=D, M=8, K=6, L=12, family="theta", max_candidates=64,
                      space=BoundedSpace(0.0, 1.0, 8.0), storage=storage)
    data = jax.random.uniform(jax.random.fold_in(rng, 1), (n, D))
    return Index.build(jax.random.fold_in(rng, 2), data, cfg)


def _batch(rng, b):
    q = jax.random.uniform(jax.random.fold_in(rng, 3), (b, D))
    w = jax.random.uniform(jax.random.fold_in(rng, 4), (b, D)) + 0.1
    return q, w


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; the host events named ``wl1.*``, as
    (name, start_ns, end_ns, args) in order of their start."""
    with jax.profiler.trace(str(tmp_path)):
        fn()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    profile = jax.profiler.ProfileData.from_file(path)
    events = [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
              for plane in profile.planes if not plane.name.startswith("/device:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith(obs.PREFIX)]
    return sorted(events, key=lambda e: (e[1], -e[2]))


def _queries(events):
    """Each wl1.query span's args, with the names and args of the query
    spans inside it."""
    out = []
    for name, s, e, args in events:
        if name == "wl1.query":
            inside = [(n, a) for n, s2, e2, a in events
                      if n.startswith("wl1.query.") and s <= s2 and e2 <= e]
            out.append((args, [n for n, _ in inside], dict(inside)))
    return out


@pytest.mark.parametrize("mode", ["exact", "probe"])
def test_query_spans_nest_and_carry_args(rng, tmp_path, mode):
    index = _index(rng, n=640 if mode == "exact" else 656)
    q, w = _batch(rng, 7)
    spec = QuerySpec(k=5, mode=mode)

    def run():
        index.query(q, w, spec).ids.block_until_ready()
        index.query(q, w, spec).ids.block_until_ready()

    first, repeat = _queries(_traced(tmp_path, run))
    for args, inner, _ in (first, repeat):
        assert inner == ["wl1.query.validate", "wl1.query.plan", "wl1.query.dispatch"]
        assert {"seq", "mode", "b", "k"} <= set(args)
        assert (args["mode"], args["b"], args["k"]) == (mode, 7, 5)
    assert repeat[0]["seq"] == first[0]["seq"] + 1
    # a new batch shape compiles once; its repeat finds the program
    assert first[2]["wl1.query.dispatch"]["compiled"] == 1
    assert repeat[2]["wl1.query.dispatch"]["compiled"] == 0


# (storage, spec, the screen's survivors the wl1.query span names)
TAILS = {
    "f32_probe": ("f32", QuerySpec(k=5), 0),
    "f32_probe_alpha": ("f32", QuerySpec(k=5, screen_alpha=2.0), 0),
    "int8_probe_screen": ("int8", QuerySpec(k=5, screen_alpha=2.0), 10),
    "int8_probe_no_screen": ("int8", QuerySpec(k=5), 0),
    "int8_exact": ("int8", QuerySpec(k=5, mode="exact", screen_alpha=2.0), 0),
}


@pytest.mark.parametrize("tail", sorted(TAILS))
def test_query_span_names_its_tail(rng, tmp_path, tail):
    """``storage`` and ``screen_keep`` on ``wl1.query`` say which tail the
    call ran: the screen's survivors, or 0 where no screen runs."""
    storage, spec, keep = TAILS[tail]
    index = _index(rng, storage=storage)
    q, w = _batch(rng, 8)
    (args, _, _), = _queries(_traced(tmp_path, lambda: index.query(q, w, spec)
                                     .ids.block_until_ready()))
    assert args["storage"] == storage
    assert args["screen_keep"] == keep


def test_collector_pass_leaves_one_span(tmp_path):
    was_on = gc.isenabled()
    gc.disable()  # no automatic pass inside the trace: only the one asked for
    try:
        events = _traced(tmp_path, gc.collect)
    finally:
        if was_on:
            gc.enable()
    passes = [e for e in events if e[0] == "wl1.gc"]
    assert len(passes) == 1
    assert passes[0][3]["generation"] == 2
    assert passes[0][3]["collected"] >= 0


def _program_text(index, q, w, spec) -> str:
    """The compiled engine program that ``index.query(q, w, spec)`` runs."""
    qspec, cfg, _ = index.resolve(spec)
    statics = pipeline.normalize_static_args(
        cfg, index.state.data.dtype, qspec.k, qspec.mode, qspec.n_probes,
        qspec.max_flips, qspec.impl, qspec.screen_alpha, qspec.early_exit,
        qspec.exit_group, qspec.exit_slack)
    lowered = pipeline._query_jit.lower(index.state, None, None, q, w,
                                        **dict(zip(STATICS, statics)))
    return lowered.compile().as_text()


PROGRAMS = {
    "exact": ("f32", QuerySpec(k=5, mode="exact"), [obs.EXACT_SCAN]),
    "probe": ("f32", QuerySpec(k=5), [obs.PROJECT, obs.WINDOW, obs.DEDUPE, obs.RERANK]),
    "multiprobe": ("f32", QuerySpec(k=5, mode="multiprobe", n_probes=4),
                   [obs.PROJECT, obs.WINDOW, obs.DEDUPE, obs.RERANK]),
    "probe_int8_screen": ("int8", QuerySpec(k=5, screen_alpha=2.0),
                          [obs.PROJECT, obs.WINDOW, obs.DEDUPE, obs.SCREEN, obs.RERANK]),
    "probe_early_exit": ("f32", QuerySpec(k=5, early_exit=True, exit_group=4),
                         [obs.PROJECT, obs.STREAM]),
}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_compiled_program_carries_its_stage_scopes(rng, program):
    storage, spec, stages = PROGRAMS[program]
    index = _index(rng, storage=storage)
    q, w = _batch(rng, 8)
    text = _program_text(index, q, w, spec)
    for stage in stages:
        assert f"/{obs.PREFIX}{stage}/" in text, stage
    absent = {obs.EXACT_SCAN, obs.PROJECT, obs.WINDOW, obs.DEDUPE, obs.SCREEN, obs.RERANK,
              obs.STREAM} - set(stages)
    for stage in absent:
        assert f"/{obs.PREFIX}{stage}/" not in text, stage
