"""The program's own tracing: ``fgl.*`` host spans and named device scopes.

Every test that opens a ``jax.profiler`` session lives in this file, since a
process holds one session at a time. The traces are read back from the
``.xplane.pb`` with ``jax.profiler.ProfileData``, as any reader of them would.
"""
import dataclasses
import glob
import os
import re
import sys

import jax
import pytest
from jax.profiler import ProfileData

from repro.core import registry
from repro.launch import compile_cache, fgl_train

ROUND_CHILDREN = ("fgl.local", "fgl.impute", "fgl.schedule", "fgl.aggregate",
                  "fgl.evaluate")
WRAPPED = re.compile(r"^(?:(?:vmap|jvp|transpose|remat|checkpoint)\()*([^()]+)\)*$")


def trainer(method, small):
    batch, cfg = small
    cfg = dataclasses.replace(cfg, local_rounds=1, imputation_interval=2,
                              ae_outer_iters=1, ae_iters=1, assessor_iters=1)
    kw = {"num_servers": 2} if method == "SpreadFGL" else {}
    return registry.build(method, cfg, batch, **kw), batch


def program_spans(trace_dir):
    """[(name, start_ns, end_ns, stats)] of the ``fgl.*`` host events."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                        for e in line.events if e.name.startswith("fgl.")]
    return sorted(out, key=lambda s: s[1])


def traced_rounds(tr, batch, rounds, trace_dir):
    state = tr.init(jax.random.key(0), batch)
    with jax.profiler.trace(str(trace_dir)):
        for _ in range(rounds):
            state, m = tr.step(state)
            jax.block_until_ready(m)
    return program_spans(trace_dir)


@pytest.fixture(scope="module")
def traces(small, tmp_path_factory):
    """Two FedAvg rounds (no imputation) and one SpreadFGL round (round 0
    imputes), each in a trace of its own."""
    out = {}
    for method, rounds in (("fedavg_fusion", 2), ("SpreadFGL", 1)):
        tr, batch = trainer(method, small)
        out[method] = traced_rounds(tr, batch, rounds, tmp_path_factory.mktemp(method))
    return out


@pytest.mark.parametrize("method,imputing_rounds", [("fedavg_fusion", ()),
                                                    ("SpreadFGL", (0,))])
def test_each_round_is_one_fgl_round_span_holding_its_dispatches(
        traces, method, imputing_rounds):
    spans = traces[method]
    rounds = [s for s in spans if s[0] == "fgl.round"]
    assert [s[3]["round"] for s in rounds] == list(range(len(rounds)))
    assert len(rounds) == (2 if method == "fedavg_fusion" else 1)
    for _, start, end, stats in rounds:
        t = stats["round"]
        assert stats["step_num"] == t
        children = [s for s in spans if s[0] != "fgl.round" and s[3].get("round") == t]
        want = [n for n in ROUND_CHILDREN if n != "fgl.impute" or t in imputing_rounds]
        assert [c[0] for c in children] == want       # one each, in dispatch order
        for _, s, e, _ in children:
            assert start <= s <= e <= end
        assert all(a[2] <= b[1] for a, b in zip(children, children[1:]))


def test_fgl_train_profile_traces_the_build_and_the_rounds(tmp_path, monkeypatch):
    """``fgl_train --profile DIR``: one ``fgl.build`` span holding its three
    steps in order, then one ``fgl.round`` per round."""
    monkeypatch.setattr(compile_cache, "enable", lambda: "")
    monkeypatch.setattr(sys, "argv", [
        "fgl_train", "--method", "fedavg_fusion", "--clients", "4", "--scale", "0.05",
        "--local-rounds", "1", "--rounds", "2", "--profile", str(tmp_path)])
    fgl_train.main()
    spans = program_spans(tmp_path)
    (build,) = [s for s in spans if s[0] == "fgl.build"]
    parts = [s for s in spans if s[0].startswith("fgl.build/")]
    assert [p[0] for p in parts] == ["fgl.build/graph", "fgl.build/partition",
                                     "fgl.build/trainer"]
    assert all(build[1] <= p[1] <= p[2] <= build[2] for p in parts)
    assert all(a[2] <= b[1] for a, b in zip(parts, parts[1:]))
    rounds = [s for s in spans if s[0] == "fgl.round"]
    assert [s[3]["round"] for s in rounds] == [0, 1]
    assert build[2] <= rounds[0][1]


# -- named scopes in the compiled programs -----------------------------------

def scopes(hlo_text):
    """Every op path component of the module's metadata, with the
    transformations around it (``vmap(generator)``) taken off."""
    out = set()
    for path in re.findall(r'op_name="([^"]*)"', hlo_text):
        for c in path.split("/"):
            m = WRAPPED.match(c)
            out.add(m.group(1) if m else c)
    return out


@pytest.fixture(scope="module")
def compiled(small):
    tr, batch = trainer("SpreadFGL", small)
    st = tr.init(jax.random.key(0), batch)
    return {
        "_local_fn": tr._local_fn.lower(st.params, st.opt_state, st.batch),
        "_agg_fn": tr._agg_fn.lower(st.params, flag=0, weights=None),
        "_eval_fn": tr._eval_fn.lower(st.params, st.batch),
        "_impute_fn": tr._impute_fn.lower(st),
        "_propagate_fn": tr._propagate_fn.lower(st.batch),
    }


@pytest.mark.parametrize("fn,module,want", [
    ("_local_fn", "jit__local_rounds", {"local_train"}),
    ("_agg_fn", "jit__aggregate", {"aggregate"}),
    ("_eval_fn", "jit__evaluate", {"evaluate"}),
    ("_impute_fn", "jit__impute", {"generator", "sim_topk", "patch", "propagate"}),
    ("_propagate_fn", "jit__propagate", {"propagate"})])
def test_compiled_programs_carry_their_scopes(compiled, fn, module, want):
    text = compiled[fn].compile().as_text()
    assert text.startswith(f"HloModule {module},")
    found = scopes(text)
    assert want <= found
    # A program carries its own scopes only: the readers split device time
    # by them.
    others = {"local_train", "aggregate", "evaluate", "generator", "sim_topk",
              "patch", "propagate"} - want
    assert not (others & found)
