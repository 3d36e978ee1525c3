"""The names the program puts on the profiler's timeline
(``repro.obs.trace``): the layer and stage scopes of every compiled
instruction of a plan, that they add no op, that the benchmark's
op-path classifier reads each instruction as it would without them, and
the ``repro.execute`` host span of ``Plan.execute`` / ``Plan.inverse``.

The compiles run in one 4-device subprocess at 64^2 (slab ``alltoall``
at P=4 and P=1, the fused ``scatter`` pipeline, the six-step 1-D
transform whose Twiddle rides a streaming Exchange), each plan compiled
twice: as written, and with ``jax.named_scope`` patched to a no-op.
"""

import json
import os
import re
import sys

import pytest

from conftest import REPO, run_subprocess

sys.path.insert(0, os.path.join(REPO, "bench"))

import trace_reduce  # noqa: E402

from repro.obs import trace as obs  # noqa: E402

_CODE = r"""
import contextlib, json, re
import jax, jax.numpy as jnp
from repro.core import plan_fft
from repro.core.compat import make_mesh

CASES = {
    "slab_alltoall_p4": (4, dict(ndim=2, backend="alltoall")),
    "slab_alltoall_p1": (1, dict(ndim=2, backend="alltoall")),
    "slab_scatter_fused_p4": (4, dict(ndim=2, backend="scatter", fuse_dft=True)),
    "six_step_scatter_p4": (4, dict(ndim=1, backend="scatter")),
}
# source locations (they name call sites, not ops)
LOCATIONS = re.compile(r"\n\nFileNames\n.*?(?=\n\n(?:ENTRY|%|HloModule)|\Z)", re.S)


def compiled(p, kw):
    mesh = make_mesh((p,), ("model",))
    plan = plan_fft((64, 64), mesh, dtype=jnp.complex64, **kw)
    kinds = [type(st).__name__ for st in plan.schedule(False).stages]
    return kinds, LOCATIONS.sub("", plan.lower().compile().as_text())


out = {}
for name, (p, kw) in CASES.items():
    kinds, scoped = compiled(p, dict(kw))
    real = jax.named_scope
    jax.named_scope = lambda _name: contextlib.nullcontext()
    try:
        _, plain = compiled(p, dict(kw))
    finally:
        jax.named_scope = real
    out[name] = {"stages": kinds, "scoped": scoped, "plain": plain}
print("RESULT " + json.dumps(out))
"""

CASES = ("slab_alltoall_p4", "slab_alltoall_p1", "slab_scatter_fused_p4", "six_step_scatter_p4")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = .*? ([a-z][a-z0-9_-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_METADATA = re.compile(r", metadata=\{[^}]*\}")
_LAYER = re.compile(r"(?:^|/)repro\.(local_fft|exchange|relayout|twiddle)(?=/|$)")
_STAGE = re.compile(r"(?:^|/)repro\.stage(\d+)\.(\w+)(?=/|$)")
#: instructions outside every scope: the argument and what XLA hoists out
#: of the shard_map body (constants, their broadcasts, bitcasts)
BOUNDARY_OPCODES = ("parameter", "constant", "bitcast")


@pytest.fixture(scope="module")
def compiled():
    out = run_subprocess(_CODE, devices=4)
    (line,) = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


def instructions(text):
    """(name, opcode, op path, custom-call target, line) per instruction."""
    out = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            path = _OP_NAME.search(line)
            target = _TARGET.search(line)
            out.append((m.group(1), m.group(2), path.group(1) if path else None,
                        target.group(1) if target else "", line))
    return out


def canonical(text):
    """The HLO without metadata, instruction names renumbered in order of
    appearance (a scope can shift XLA's numbering of identical ops)."""
    names = {}
    return re.sub(r"%([\w.-]+)",
                  lambda m: "%" + names.setdefault(m.group(1), f"v{len(names)}"),
                  _METADATA.sub("", text))


def boundary(opcode, line):
    return opcode in BOUNDARY_OPCODES or (
        opcode == "broadcast" and re.search(r"broadcast\(%constant", line) is not None)


@pytest.mark.parametrize("case", CASES)
def test_every_op_has_one_layer_scope(compiled, case):
    seen = set()
    for name, opcode, path, _, line in instructions(compiled[case]["scoped"]):
        if path is None:
            continue
        layers = _LAYER.findall(path)
        if not layers:
            assert boundary(opcode, line), (name, opcode, path)
            continue
        assert len(layers) == 1, (name, path)
        seen.add(layers[0])
    assert "local_fft" in seen and "relayout" in seen
    if case.endswith("p4"):
        assert "exchange" in seen
    if case.startswith("six_step"):
        assert "twiddle" in seen


@pytest.mark.parametrize("case", CASES)
def test_stage_scopes_name_the_schedule(compiled, case):
    kinds = compiled[case]["stages"]
    indices = set()
    for name, _, path, _, _ in instructions(compiled[case]["scoped"]):
        if path is None or not _LAYER.search(path):
            continue
        stages = _STAGE.findall(path)
        assert stages, (name, path)
        for index, kind in stages:
            assert kinds[int(index)] == kind, (name, path, kinds)
            indices.add(int(index))
    compute = {i for i, k in enumerate(kinds) if k in ("LocalFFT", "Exchange", "Twiddle")}
    assert compute <= indices, (kinds, sorted(indices))


@pytest.mark.parametrize("case", CASES)
def test_scopes_add_no_op(compiled, case):
    scoped, plain = compiled[case]["scoped"], compiled[case]["plain"]
    assert "repro." in scoped and "repro." not in plain
    assert canonical(scoped) == canonical(plain)


@pytest.mark.parametrize("case", CASES)
def test_op_class_unchanged_by_scopes(compiled, case):
    scoped = instructions(compiled[case]["scoped"])
    plain = instructions(compiled[case]["plain"])
    assert len(scoped) == len(plain)
    for (n1, op1, p1, t1, _), (n2, op2, p2, t2, _) in zip(scoped, plain):
        assert (op1, t1) == (op2, t2)
        assert trace_reduce.op_class(n1, op1, p1 or "", t1) == trace_reduce.op_class(
            n2, op2, p2 or "", t2), (n1, p1, p2)


def test_scope_names_avoid_the_op_path_patterns():
    stage_names = [f"{obs.PREFIX}stage{i}.{k}" for i, k in enumerate(
        ("LocalFFT", "LocalR2C", "LocalC2R", "HermitianPack", "Trim", "Relayout",
         "Twiddle", "Exchange"))]
    for name in obs.LAYERS + tuple(stage_names):
        assert name.startswith(obs.PREFIX)
        path = f"jit(<lambda>)/shard_map/{name}/transpose"
        assert trace_reduce.op_class("copy.1", "copy", path) == "relayout", name


def test_only_obs_trace_names_spans_and_scopes():
    src = os.path.join(REPO, "src", "repro")
    found = []
    for root, _, files in os.walk(src):
        for f in files:
            path = os.path.join(root, f)
            if f.endswith(".py") and path != obs.__file__:
                with open(path) as fh:
                    text = fh.read()
                if "TraceAnnotation" in text or "named_scope" in text:
                    found.append(os.path.relpath(path, src))
    assert found == []


# ---------------------------------------------------------------------------
# repro.execute, through the helper (no live profiler)
# ---------------------------------------------------------------------------


@pytest.fixture
def entered(monkeypatch):
    """Every span entered through ``obs.span``: (name, args)."""
    calls = []
    real = obs.span

    def counting(name, **args):
        calls.append((name, args))
        return real(name, **args)

    monkeypatch.setattr(obs, "span", counting)
    return calls


@pytest.fixture(scope="module")
def plan():
    import jax.numpy as jnp

    from repro.core import plan_fft
    from repro.core.compat import make_mesh

    return plan_fft((16, 16), make_mesh((1,), ("model",)), backend="alltoall",
                    dtype=jnp.complex64)


def test_execute_enters_one_span_per_call(plan, entered):
    import numpy as np

    x = np.ones((16, 16), np.complex64)
    before = plan.calls
    y = plan.execute(x)
    plan.execute(x)
    plan.inverse(y)
    assert [n for n, _ in entered] == [obs.EXECUTE] * 3
    assert [a["call"] for _, a in entered] == [before + 1, before + 2, before + 3]
    assert plan.calls == before + 3


def test_span_off_is_one_shared_noop(monkeypatch):
    import jax

    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert obs.span(obs.EXECUTE, call=1) is obs.span("repro.other")
    with obs.span(obs.EXECUTE, call=1):
        pass


def test_span_on_is_a_profiler_annotation(monkeypatch):
    import jax

    made = []

    class Recording:
        @staticmethod
        def is_enabled():
            return True

        def __init__(self, name, **args):
            made.append((name, args))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recording)
    with obs.span(obs.EXECUTE, call=7):
        pass
    assert made == [(obs.EXECUTE, {"call": 7})]


def test_recorder_span_enters_the_profiler_span(entered):
    rec = obs.TraceRecorder()
    with rec.span("row:x", cat="exchange", backend="scatter"):
        pass
    assert entered == [("repro.row:x", {"cat": "exchange", "backend": "scatter"})]
    assert [s.name for s in rec.spans] == ["row:x"]
