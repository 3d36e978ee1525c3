"""The names the program puts on the profiler's timeline
(``repro.obs.trace``): the layer and stage scopes of every compiled
instruction of a plan, that they add no op, that the benchmark's
op-path classifier reads each instruction as it would without them, and
the ``repro.execute`` host span of ``Plan.execute`` / ``Plan.inverse``.

The compiles run in one 4-device subprocess at 64^2 (slab ``alltoall``
at P=4 and P=1, the fused ``scatter`` pipeline, the six-step 1-D
transform whose Twiddle rides a streaming Exchange), each plan compiled
twice: as written, and with ``jax.named_scope`` patched to a no-op. The
same subprocess compiles NPB FT's own programs (``repro.apps.npb_ft``:
the fused iteration and the entry into planes) on a P=1 slab and a 2x2
pencil at 16x32x64.
"""

import hashlib
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

from repro.apps.npb_ft import NPBFT

APP_MESHES = {
    "npb_slab_p1": (make_mesh((1,), ("model",)), "slab"),
    "npb_pencil_2x2": (make_mesh((2, 2), ("rows", "cols")), "pencil"),
}
for name, (mesh, decomp) in APP_MESHES.items():
    plan = plan_fft((16, 32, 64), mesh, ndim=3, decomp=decomp, backend="alltoall",
                    dtype=jnp.complex64)
    app = NPBFT(plan)
    out[name] = {step: LOCATIONS.sub("", low.compile().as_text())
                 for step, low in app.lower().items()}
print("RESULT " + json.dumps(out))
"""

CASES = ("slab_alltoall_p4", "slab_alltoall_p1", "slab_scatter_fused_p4", "six_step_scatter_p4")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = .*? ([a-z][a-z0-9_-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_METADATA = re.compile(r", metadata=\{[^}]*\}")
_LAYER = re.compile(
    r"(?:^|/)repro\.(local_fft|exchange|relayout|twiddle|evolve|checksum)(?=/|$)")
_STAGE = re.compile(r"(?:^|/)repro\.stage(\d+)\.(\w+)(?=/|$)")
APP_CASES = ("npb_slab_p1", "npb_pencil_2x2")
#: sha256 of the paper plans' compiled HLO on this CPU backend (the 64^2
#: stand-ins of ``paper_fft2_16k_p1``/``_p4``) once metadata is stripped
#: and names renumbered (:func:`canonical`): a change that moves them
#: changes what the paper cells run, and says so here
PAPER_PLAN_DIGESTS = {
    "slab_alltoall_p1": "fe748e9d0bdd02d89b66d9c359a985fd2c752ddede37ee7b8549d89165629f8e",
    "slab_alltoall_p4": "fe89e15e6b7cbab63a038feae1921b2c669173ec2a8d8273308b6827f22b2492",
}
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


@pytest.mark.parametrize("case", APP_CASES)
@pytest.mark.parametrize("step", ["evolve", "checksum", "entry"])
def test_app_ops_have_one_layer_scope(compiled, case, step):
    """Every op of NPB FT's programs with a layer scope has exactly one:
    in the fused ``jit_iterate``, the ops of evolve and the checksum lie
    under their own scopes and the op-path classifier reads them as
    relayout (the checksum's psum as the exchange); the entry program
    ``jit_to_planes`` is relayout alone."""
    program, module = ("entry", "jit_to_planes") if step == "entry" else ("iterate", "jit_iterate")
    layer = "relayout" if step == "entry" else step
    text = compiled[case][program]
    assert re.match(rf"HloModule {module}\b", text), text.splitlines()[0]
    seen = 0
    for name, opcode, path, target, line in instructions(text):
        if path is None:
            continue
        layers = _LAYER.findall(path)
        if not layers:
            assert boundary(opcode, line), (name, opcode, path)
            continue
        assert len(layers) == 1, (name, path)
        if step == "entry":
            assert layers == [layer], (name, path)
        if layers != [layer]:
            continue
        # the checksum's psum is a collective, and counts as one
        want = "exchange" if opcode.startswith("all-reduce") else "relayout"
        assert trace_reduce.op_class(name, opcode, path, target) == want, (name, path)
        seen += 1
    assert seen


@pytest.mark.parametrize("case", sorted(PAPER_PLAN_DIGESTS))
def test_paper_plans_hlo_unchanged(compiled, case):
    text = canonical(compiled[case]["plain"])
    assert hashlib.sha256(text.encode()).hexdigest() == PAPER_PLAN_DIGESTS[case]


def test_scope_names_avoid_the_op_path_patterns():
    stage_names = [f"{obs.PREFIX}stage{i}.{k}" for i, k in enumerate(
        ("LocalFFT", "LocalR2C", "LocalC2R", "HermitianPack", "Trim", "Relayout",
         "Twiddle", "Exchange"))]
    for name in obs.LAYERS + tuple(stage_names):
        assert name.startswith(obs.PREFIX)
        path = f"jit(<lambda>)/shard_map/{name}/transpose"
        assert trace_reduce.op_class("copy.1", "copy", path) == "relayout", name
    # the op paths of evolve and the checksum in the fused iteration
    for path in (f"jit(iterate)/{obs.EVOLVE}/mul",
                 f"jit(iterate)/jit(checksum)/{obs.CHECKSUM}/mul"):
        assert trace_reduce.op_class("fusion.1", "fusion", path) == "relayout", path


def test_only_obs_trace_names_spans_and_scopes():
    src = os.path.join(REPO, "src", "repro")
    found = []
    for root, _, files in os.walk(src):
        for f in files:
            path = os.path.join(root, f)
            if f.endswith(".py") and path != obs.__file__:
                with open(path) as fh:
                    text = fh.read()
                quoted = [f'"{n}"' for n in obs.LAYERS] + [f"'{n}'" for n in obs.LAYERS]
                if ("TraceAnnotation" in text or "named_scope" in text
                        or any(q in text for q in quoted)):
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
