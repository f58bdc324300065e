"""The model layer imports down, never sideways: a module of
``horovod_tpu/models/`` takes the shared parts from ``models.layers`` and
``models.attention`` and no name from another decoder, except
``hybrid_moe``, which calls ``hybrid_ssm``'s Mamba-2 mixer; and which
implementation a kernel takes is decided in one module,
``ops.backend``."""

import ast
import pathlib

import pytest

PACKAGE = pathlib.Path(__file__).resolve().parent.parent / "horovod_tpu"
MODELS = PACKAGE / "models"
# decoders no module may take a name from
DECODERS = ("block_diffusion", "looped", "kimi_linear", "hybrid_moe",
            "gated_conv")
# what ``hybrid_moe`` may take from ``hybrid_ssm``: the mixer
MIXER = {"mamba_mixer", "mixer_start"}


def taken(path):
    """``(sibling module, name)`` for every name ``path`` takes from a
    module of ``models/``: ``from .m import n``, and ``m.n`` where it
    imports ``m`` whole."""
    tree = ast.parse(path.read_text())
    whole, names = {}, []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ImportFrom):
            continue
        if node.level == 1:
            module = node.module
        elif node.level == 0 and (node.module or "").startswith(
                "horovod_tpu.models"):
            module = node.module[len("horovod_tpu.models"):].lstrip(".")
            module = module or None
        else:
            continue
        if module is None:
            whole.update({a.asname or a.name: a.name for a in node.names})
        else:
            names += [(module, a.name) for a in node.names]
    names += [(whole[node.value.id], node.attr) for node in ast.walk(tree)
              if isinstance(node, ast.Attribute)
              and isinstance(node.value, ast.Name)
              and node.value.id in whole]
    return names


@pytest.mark.parametrize("name", sorted(p.name for p in MODELS.glob("*.py")))
def test_no_model_takes_a_name_from_another_decoder(name):
    for module, attr in taken(MODELS / name):
        assert module not in DECODERS, (module, attr)
        if module == "hybrid_ssm":
            assert (name, attr in MIXER) == ("hybrid_moe.py", True), attr


def test_the_kernels_path_choice_has_one_home(monkeypatch):
    """``pallas_ops`` keeps no reader of its own, no module of the
    package names one, and ``backend.pallas_mode`` is what it was:
    ``HVTPU_PALLAS=0`` turns the kernels off, ``HVTPU_PALLAS_INTERPRET=1``
    interprets them, else they run where the backend is a TPU."""
    from horovod_tpu.ops import backend, pallas_ops

    assert not hasattr(pallas_ops, "_pallas_mode")
    assert not hasattr(pallas_ops, "_on_tpu")
    assert not [p for p in PACKAGE.rglob("*.py")
                if "_pallas_mode" in p.read_text() or "_on_tpu" in
                p.read_text()]
    monkeypatch.delenv("HVTPU_PALLAS", raising=False)
    monkeypatch.delenv("HVTPU_PALLAS_INTERPRET", raising=False)
    for tpu in (False, True):
        monkeypatch.setattr(backend, "on_tpu", lambda: tpu)
        assert backend.pallas_mode() == (tpu, False)
        monkeypatch.setenv("HVTPU_PALLAS_INTERPRET", "1")
        assert backend.pallas_mode() == (True, True)
        monkeypatch.setenv("HVTPU_PALLAS", "0")
        assert backend.pallas_mode() == (False, False)
        monkeypatch.delenv("HVTPU_PALLAS")
        monkeypatch.delenv("HVTPU_PALLAS_INTERPRET")
