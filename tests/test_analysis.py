"""Tests for ``repro.analysis`` — the secret-flow taint, lock-discipline
and retrace-stability passes — plus the redaction satellites they gate.

The fixture corpus under ``tests/analysis_fixtures/`` is *parsed*, never
imported: each file carries deliberately injected violations whose exact
``(rule, line)`` locations are pinned here, so a regression in any pass
shows up as a missed or misplaced finding.

``test_self_gate_src_repro_is_clean`` is the tier-1 self-gate from the
issue: all three passes over the real ``src/repro`` tree must report zero
non-declassified findings, and every declassification must carry a
written reason.
"""
from __future__ import annotations

import ast
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.analysis import run_paths
from repro.analysis.base import Module, extract_annotations
from repro.analysis import locks, retrace, taint

from _hypothesis_compat import given, settings, st

FIXTURES = Path(__file__).parent / "analysis_fixtures"
SRC = Path(__file__).parent.parent / "src" / "repro"


def _findings(path, declassified=False):
    active, decl, errors = run_paths([path])
    assert not errors, [e.render() for e in errors]
    return decl if declassified else active


def _locset(findings):
    return {(f.rule, f.line) for f in findings}


# ---------------------------------------------------------------------------
# fixture corpus: exact finding locations per pass
# ---------------------------------------------------------------------------

FIXTURE_EXPECTATIONS = [
    ("leaky_log.py", {("log-leak", 10), ("log-leak", 15)}),
    ("secret_in_exception.py",
     {("exception-leak", 8), ("assert-leak", 13)}),
    ("wire_header_leak.py", {("wire-leak", 8), ("wire-leak", 12)}),
    ("wire_result_parts_leak.py", {("wire-leak", 9), ("wire-leak", 13)}),
    ("decode_frames_leak.py", {("scores-leak", 10), ("wire-leak", 15)}),
    ("declassified_snapshot.py", {("serialized-secret", 14)}),
    ("lock_violation.py",
     {("held-forbidden", 17), ("held-forbidden", 21),
      ("requires-lock", 28)}),
    ("retrace_hazard.py",
     {("wall-clock", 13), ("value-dependent-branch", 14),
      ("value-dependent-shape", 16), ("concretization", 16),
      ("unordered-iteration", 23), ("value-dependent-shape", 33)}),
]


@pytest.mark.parametrize("fixture,expected",
                         FIXTURE_EXPECTATIONS,
                         ids=[f for f, _ in FIXTURE_EXPECTATIONS])
def test_fixture_findings_at_exact_locations(fixture, expected):
    found = _locset(_findings(FIXTURES / fixture))
    assert found == expected


def test_result_parts_sink_flags_a_tainted_argument():
    """``encode_result_parts`` writes its payload to the socket as a view:
    a secret passed to it is a wire leak, named after that sink; the same
    call on a plain result is not."""
    (f, _) = sorted(_findings(FIXTURES / "wire_result_parts_leak.py"),
                    key=lambda f: f.line)
    assert (f.rule, f.line) == ("wire-leak", 9)
    assert "encode_result_parts()" in f.message
    assert "encode_result_parts" in taint.WIRE_SINKS
    active, _, _ = _run_source(
        "from repro.runtime import wire\n"
        "def send(seed, rid):\n"
        "    return wire.encode_result_parts(rid, seed)\n"
        "def fine(rid, res):\n"
        "    return wire.encode_result_parts(rid, res)\n"
    )
    assert _locset(active) == {("wire-leak", 3)}


def test_declassified_fixture_is_suppressed_with_reason():
    decl = _findings(FIXTURES / "declassified_snapshot.py", declassified=True)
    assert _locset(decl) == {("serialized-secret", 10)}
    (f,) = decl
    assert "checkpoint" in f.declassified


def test_fixture_clean_functions_stay_clean():
    # The `fine()` controls in each fixture must not add findings beyond
    # the pinned expectations (covered by exact-set equality above); the
    # pinned sets themselves must each name at least one real violation.
    for fixture, expected in FIXTURE_EXPECTATIONS:
        assert expected, fixture


# ---------------------------------------------------------------------------
# the self-gate: src/repro is clean, declassifications are audited
# ---------------------------------------------------------------------------

def test_self_gate_src_repro_is_clean():
    active, declassified, errors = run_paths([SRC])
    assert not errors, [e.render() for e in errors]
    assert active == [], "\n".join(f.render() for f in active)
    # every legitimate secret flow is annotated WITH a reason
    assert len(declassified) >= 5
    for f in declassified:
        assert f.declassified and len(f.declassified) > 10, f.render()


def test_driver_exit_code_bitmask():
    env_script = (
        "import sys; sys.path.insert(0, 'src'); "
        "from repro.analysis import main; "
        "sys.exit(main(['tests/analysis_fixtures/lock_violation.py',"
        "'tests/analysis_fixtures/retrace_hazard.py']))"
    )
    proc = subprocess.run(
        [sys.executable, "-c", env_script],
        cwd=Path(__file__).parent.parent, capture_output=True, text=True,
    )
    assert proc.returncode == locks.BIT | retrace.BIT, proc.stdout
    proc2 = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, 'src'); "
         "from repro.analysis import main; "
         "sys.exit(main(['tests/analysis_fixtures/leaky_log.py', '--json']))"],
        cwd=Path(__file__).parent.parent, capture_output=True, text=True,
    )
    assert proc2.returncode == taint.BIT, proc2.stdout
    import json
    report = json.loads(proc2.stdout)
    assert report["counts"]["active"] == 2
    assert all(f["pass"] == "taint" for f in report["findings"])


def test_empty_declassification_reason_is_an_error():
    src = (
        "def snapshot_state(sess):\n"
        "    # analysis: declassified()\n"
        "    return {}, {'perm': sess.morpher.perm}\n"
    )
    active, decl, errors = _run_source(src)
    # not suppressed: the finding stays active AND the annotation errors
    assert _locset(active) == {("serialized-secret", 3)}
    assert decl == []
    assert [(e.rule, e.line) for e in errors] == [("empty-reason", 2)]


def test_unknown_annotation_kind_is_an_error():
    src = "x = 1  # analysis: declasified(typo)\n"
    active, decl, errors = _run_source(src)
    assert [(e.rule,) for e in errors] == [("unknown-kind",)]


# ---------------------------------------------------------------------------
# in-memory analysis helper (also used by the hypothesis sweep)
# ---------------------------------------------------------------------------

def _run_source(source, path="generated.py"):
    module = Module(
        path=path,
        tree=ast.parse(source),
        lines=source.splitlines(),
        annotations=extract_annotations(source),
    )
    from repro.analysis.driver import PASSES, _annotation_findings

    errors = _annotation_findings([module])
    active, decl = [], []
    for p in PASSES:
        for f in p.run([module]):
            (decl if f.declassified is not None else active).append(f)
    return active, decl, errors


CLEAN_SNIPPETS = [
    # plain logging of public facts
    "def f{i}(log, sess):\n"
    "    log.info('tenant ready, vocab=%d', sess.morpher.perm.shape[0])\n",
    # shape-only error text
    "def f{i}(x):\n"
    "    if x.shape[0] == 0:\n"
    "        raise ValueError(f'empty batch of shape {{x.shape}}')\n",
    # redacted repr built from sanitizers
    "def f{i}(sess):\n"
    "    from repro.core.redact import describe_array\n"
    "    return f'perm={{describe_array(sess.morpher.perm)}}'\n",
    # lock discipline respected
    "class C{i}:\n"
    "    def work(self):\n"
    "        with self._cv:\n"
    "            self.note()\n"
    "    def note(self):\n"
    "        self.count = 1\n",
    # jit step branching on statics and shapes only
    "import jax\n"
    "from functools import partial\n"
    "@partial(jax.jit, static_argnames=('mode{i}',))\n"
    "def step{i}(x, mode{i}):\n"
    "    if mode{i} == 'a':\n"
    "        return x * 2\n"
    "    return x.reshape(x.shape[0], -1)\n",
    # comprehension over public data
    "def f{i}(rows):\n"
    "    return [r * 2 for r in rows if r.size]\n",
]


@settings(max_examples=25, deadline=None)
@given(st.lists(st.sampled_from(CLEAN_SNIPPETS), min_size=1, max_size=6))
def test_generated_clean_modules_have_zero_findings(snippets):
    source = "\n".join(s.format(i=i) for i, s in enumerate(snippets))
    active, decl, errors = _run_source(source)
    assert active == [], "\n".join(f.render() for f in active)
    assert errors == []


# ---------------------------------------------------------------------------
# redaction satellites: reprs carry no payload bytes
# ---------------------------------------------------------------------------

def test_repr_of_registered_session_contains_no_payload_bytes():
    from repro.core.lm import LMSessionRegistry

    marker = 12345.678  # distinctive payload value
    vocab, d_model = 64, 16
    emb = np.full((vocab, d_model), marker, np.float32)
    reg = LMSessionRegistry(capacity=4, vocab=vocab, d_model=d_model)
    reg.register("t0", emb, seed=7)
    sess = reg.session("t0")

    for obj in (reg, sess, sess.morpher):
        r = repr(obj)
        assert "12345" not in r, r
        assert "array(" not in r, r       # no numpy array dumps at all
    # the session repr still identifies the arrays structurally
    assert f"({vocab}, {d_model})" in repr(sess)
    assert "#" in repr(sess.morpher)      # digest present


def test_repr_of_morph_core_is_redacted():
    from repro.core.morphing import make_core, materialize_M

    core = make_core(3, 16, 4)
    r = repr(core)
    assert "array(" not in r and "[" not in r, r
    # but the actual matrix is intact and usable
    assert np.asarray(materialize_M(core)).shape == (16, 16)
    # digest distinguishes two different secrets
    other = make_core(4, 16, 4)
    assert repr(other) != r


def test_vision_registry_repr_is_redacted():
    from repro.core.d2r import ConvGeometry
    from repro.core.protocol import SessionRegistry

    geom = ConvGeometry(alpha=2, beta=4, m=6, p=3)
    reg = SessionRegistry(geom, kappa=2, capacity=2)
    kernels = np.ones((geom.alpha, geom.beta, geom.p, geom.p), np.float32)
    reg.register("a", kernels, seed=1)
    r = repr(reg)
    assert "SessionRegistry" in r and "tenants=1" in r
    assert "array(" not in r


# ---------------------------------------------------------------------------
# client teardown errors are recorded, not swallowed
# ---------------------------------------------------------------------------

def test_fleet_report_records_close_error_classes():
    from repro.launch.client import ClientFleet, FleetConfig, _Chan

    fleet = ClientFleet(FleetConfig(port=1))

    class _BoomWriter:
        def close(self):
            raise RuntimeError("boom")

    chan = _Chan(fleet, 0)
    chan.writer = _BoomWriter()
    chan._drop()
    assert fleet.report.conn_drops == 1
    assert fleet.report.close_errors == {"RuntimeError": 1}
    assert fleet.report.as_dict()["close_errors"] == {"RuntimeError": 1}
