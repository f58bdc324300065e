"""``benchmark/passes.py`` and its eight readers on the traces the
repository keeps (``data/PROVENANCE*.txt``): a step by pass adds up to
the time an op ran, and the framework's own scopes are held by a pair
recorded on four chips from PR 36's tree."""

import gzip
import os
import types

import pytest

from benchmark import cells, passes, scopes, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DP4 = "vgg16-b64-dp4.pr36"
NEW_METRICS = [
    "forward_ms_per_step", "recompute_ms_per_step", "backward_ms_per_step",
    "outside_grad_ms_per_step", "optimizer_ms_per_step",
    "exchange_ms_per_step", "fusion_copy_ms_per_step", "unnamed_ms_per_step"]
# device ms a step (forward, recomputed, backward, rest, unnamed) of the
# three programs recorded by PRs 34, 32 and 27, read apart from the code
# under test: the same rule written as one loop over the extract's op
# times and the lines of the text
RECORDED = {
    "ouro-2.6b-6of48-t8k-b1.12steps": (226.5, 219.4, 508.2, 25.6, 29.6),
    "granite-4.0-h-micro-10of40-t8k-b2.5steps": (
        233.2, 220.2, 472.0, 39.2, 54.0),
    "sdar-30b-a3b-1of8-t8k-b2.5steps": (121.6, 57.2, 198.4, 27.5, 45.9),
}


def recorded(name, text_name=None):
    """(reduction, text) of a recorded pair; the text is the lines of
    the compiled step that name an op of the extract."""
    reduction = xplane.reduce(
        xplane.load_extract(os.path.join(DATA, name + ".json.gz")))
    text_name = text_name or name.rsplit(".", 1)[0]
    with gzip.open(os.path.join(DATA, text_name + ".hlo-lines.txt.gz"),
                   "rt") as f:
        return reduction, f.read()


def busy_ms(reduction):
    """A step's busy time, a chip over its own kept periods, mean over
    the chips."""
    return sum(d.busy_ns / len(d.step_ns) for d in reduction.devices) / (
        1e6 * len(reduction.devices))


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_the_five_parts_add_up_to_the_time_an_op_ran(name):
    reduction, text = recorded(name)
    parts = passes.ms_per_step(reduction, text)
    assert tuple(parts) == passes.PASSES
    assert sum(parts.values()) == pytest.approx(
        busy_ms(reduction), rel=1e-4)
    assert [parts[p] for p in passes.PASSES] == pytest.approx(
        RECORDED[name], abs=0.05)
    # the same time by scope, as ``scopes.py`` has it
    by_scope = scopes.ms_per_step(reduction, text)
    cells_ = passes.table(reduction, text)
    for scope, ms in by_scope.items():
        assert sum(v for (_, s), v in cells_.items() if s == scope) == (
            pytest.approx(ms))
    # the layers of these three models are checkpointed whole: about a
    # fifth of the step is made twice
    assert 0.12 < parts[passes.RECOMPUTE] / sum(parts.values()) < 0.23
    line = passes.account(reduction, text)
    assert line.startswith("passes: device ms a step by pass: forward ")
    assert "(+0.00 %)" in line or "(-0.00 %)" in line


@pytest.mark.parametrize("name", ["resnet50-b256-dp1.12steps",
                                  "vgg16-b64-dp4.12steps"])
def test_an_extract_without_its_text_reads_nothing(name):
    reduction = xplane.reduce(
        xplane.load_extract(os.path.join(DATA, name + ".json.gz")))
    assert reduction is not None
    obs = types.SimpleNamespace(trace=reduction, compiled_text=None)
    assert passes.ms_per_step(reduction, None) is None
    assert passes.account(reduction, None) is None
    for metric in NEW_METRICS:
        assert cells.load_metric("per_layer", metric).read(obs) is None


def test_a_program_before_the_scopes_reads_its_passes_and_no_framework_part():
    """What a parent commit gives the driver: the pass is JAX's own
    naming and is read; the framework's scopes are not there, and their
    three metrics are left out of the line without an error."""
    reduction, text = recorded("sdar-30b-a3b-1of8-t8k-b2.5steps")
    obs = types.SimpleNamespace(trace=reduction, compiled_text=text)
    read = {name: cells.load_metric("per_layer", name).read(obs)
            for name in NEW_METRICS}
    assert read["forward_ms_per_step"] == pytest.approx(121.6, abs=0.05)
    assert read["unnamed_ms_per_step"] == pytest.approx(45.9, abs=0.05)
    for name in ("optimizer_ms_per_step", "exchange_ms_per_step",
                 "fusion_copy_ms_per_step"):
        assert read[name] is None


@pytest.fixture(scope="module")
def dp4():
    reduction, text = recorded(DP4 + ".5steps", DP4)
    return types.SimpleNamespace(trace=reduction, compiled_text=text)


def test_the_exchange_scopes_on_four_chips(dp4, capsys):
    """Five steps of ``vgg16-b64-dp4`` traced on the four-chip host from
    PR 36's tree, and the lines of its compiled step
    (``data/PROVENANCE-pr36.txt``)."""
    assert len(dp4.trace.devices) == 4
    read = {name: cells.load_metric("per_layer", name).read(dp4)
            for name in NEW_METRICS}
    line = capsys.readouterr().out
    assert line.startswith("passes: device ms a step by pass: ")
    by_scope = scopes.ms_per_step(dp4.trace, dp4.compiled_text)
    assert set(passes.FRAMEWORK) >= {
        s[:s.index(".") + 1] for s in by_scope if s != scopes.UNSCOPED}
    # the parts are the step
    parts = [read[n] for n in (
        "forward_ms_per_step", "backward_ms_per_step",
        "outside_grad_ms_per_step", "unnamed_ms_per_step")]
    assert read["recompute_ms_per_step"] == 0.0     # nothing checkpointed
    assert sum(parts) == pytest.approx(busy_ms(dp4.trace), rel=1e-4)
    # the exchange holds the collectives that ``xplane.py`` times from
    # outside, and the copies are a part of it
    assert read["exchange_ms_per_step"] >= (
        dp4.trace.collective_ms_per_step)
    assert 0.0 < read["fusion_copy_ms_per_step"] < (
        read["exchange_ms_per_step"] - dp4.trace.collective_ms_per_step
        + 0.5)
    assert read["fusion_copy_ms_per_step"] == pytest.approx(
        by_scope["hvtpu:exchange.pack"] + by_scope["hvtpu:exchange.unpack"])
    assert read["optimizer_ms_per_step"] == pytest.approx(
        by_scope["hvtpu:optimizer.guard"]
        + by_scope["hvtpu:optimizer.update"])
    # the framework's part is outside the gradient
    assert read["outside_grad_ms_per_step"] >= (
        read["exchange_ms_per_step"] + read["optimizer_ms_per_step"])


def test_the_fusions_of_more_than_one_scope_on_four_chips(dp4):
    """The lines kept with the extract hold the bodies of its fusions,
    so the error bar is held by data too: the guard's ``is-finite``
    reductions hold the exchange's division by four."""
    program = passes.parse(dp4.compiled_text)
    guard = [body for fusion, body in program.bodies.items()
             if fusion.startswith("is-finite_reduce_fusion")]
    assert guard and any(
        {passes.scope_of(n) for n in body} == {
            "hvtpu:exchange.reduce", "hvtpu:optimizer.guard"}
        for body in guard)
    mixed = passes.mixed_fusion_ms(dp4.trace, dp4.compiled_text)
    assert 0.0 < mixed["by_scope"] <= mixed["either"]
    assert mixed["by_pass"] <= mixed["either"] <= (
        mixed["by_scope"] + mixed["by_pass"])
    assert mixed["either"] < busy_ms(dp4.trace)
