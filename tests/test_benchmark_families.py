"""The benchmark's model families, guarded in tier 1 (``benchmark/tests``
is outside the tier-1 command): the two guards PR 36 wrote to be lifted
(``benchmark/tests/test_families.py``), over every configuration and
every family, and the one place where the benchmark's table of a
family's tensors meets the server's loader.

Imports no JAX at module level; ``benchmark/`` goes on the path as
``benchmark/tests/conftest.py`` puts it.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path.insert(0, str(BENCH))

import checkpoint  # noqa: E402
import run  # noqa: E402

# ``run.py`` puts ``benchmark/`` first on the path as well; it has to be
# there (families, readers and lengths are found by name when called) but
# LAST: ``benchmark/tests`` must not shadow this directory as ``tests``
# for the test files this worker imports next
while str(BENCH) in sys.path:
    sys.path.remove(str(BENCH))
sys.path.append(str(BENCH))

CONFIGS = sorted(p.stem for p in (BENCH / "configs").glob("*.json"))
FAMILIES = sorted(p.stem for p in (BENCH / "families").glob("*.py"))


def served_config(name: str) -> tuple[dict, dict]:
    """(the configuration file, the ``config.json`` it is served with)."""
    full = run.load("configs", name)
    return full, run.served_config(full)


def test_every_family_is_here():
    assert {"llama", "falcon_h1", "deepseek_v2",
            "cohere2_moe"} <= set(FAMILIES)
    assert {run.load("configs", c)["family"] for c in CONFIGS} == {
        "llama", "falcon_h1", "deepseek_v2", "cohere2_moe"}


@pytest.mark.parametrize("config", CONFIGS)
def test_every_configuration_names_a_family_that_fits_its_widths(config):
    """The module is there, its table has a layer for every layer and a
    tensor with the vocabulary's rows, and every matrix has the residual
    width (or a low rank the configuration names) on one side."""
    full, hf = served_config(config)
    assert "family" in full and "family" not in hf
    top, layers = run.load_family(full["family"]).tensors(hf)
    assert top and len(layers) == hf["num_hidden_layers"] and all(layers)
    shapes = [shape for table in (top, *layers)
              for shape, _ in table.values()]
    assert any(shape[0] == hf["vocab_size"] for shape in shapes)
    # a low-rank projection's second factor (deepseek_v2's q_b_proj and
    # kv_b_proj) has its rank where other matrices have the residual
    # width
    widths = {hf["hidden_size"], hf.get("q_lora_rank"),
              hf.get("kv_lora_rank")} - {None}
    for shape in shapes:
        if len(shape) == 2:
            assert widths & set(shape), shape


@pytest.mark.parametrize("family", FAMILIES)
def test_a_family_module_holds_the_three_things(family):
    module = run.load_family(family)
    assert callable(module.tensors) and callable(module.score)
    assert 0 < module.LOGPROB_TOLERANCE < 1


@pytest.fixture(scope="module",
                params=["rehearsal", "falcon-h1-tiny", "deepseek-v2-tiny",
                        "cohere2-moe-tiny"])
def written(request, tmp_path_factory):
    """A checkpoint of each family, written by ``ensure_checkpoint``."""
    if request.param == "rehearsal":
        full, hf = served_config("rehearsal")
    elif request.param == "deepseek-v2-tiny":
        from tests.fixture_models import TINY_DEEPSEEK_V2_CONFIG

        # the family's configurations are one chip's share: of the
        # router's 4 x 4 experts the first group's four are held
        full = {"family": "deepseek_v2", "dtype": "float32"}
        hf = dict(TINY_DEEPSEEK_V2_CONFIG, n_routed_experts=4)
    elif request.param == "cohere2-moe-tiny":
        from tests.fixture_models import TINY_COHERE2_MOE_CONFIG

        # one chip's share: of the router's 128 experts the first two
        full = {"family": "cohere2_moe", "dtype": "float32"}
        hf = dict(TINY_COHERE2_MOE_CONFIG, num_experts=2)
    else:
        from tests.fixture_models import TINY_FALCON_H1_CONFIG

        full = {"family": "falcon_h1", "dtype": "float32"}
        hf = dict(TINY_FALCON_H1_CONFIG)
    family = run.load_family(full["family"])
    path, _ = checkpoint.ensure_checkpoint(
        tmp_path_factory.mktemp("work"), request.param, hf, 5,
        full["dtype"], family.tensors)
    return path, hf, family


def test_family_is_not_in_the_served_config(written):
    path, _, _ = written
    assert "family" not in json.loads((path / "config.json").read_text())
    assert "family" not in json.loads(
        (path / checkpoint.MARKER).read_text())["config"]


def test_the_servers_loader_takes_the_familys_checkpoint(written):
    """What the family's table writes is what the server's loader reads:
    every tensor is asked for and none is left over, and the parameters
    come out at the model's shapes."""
    import jax

    from vllm_tgis_adapter_tpu.engine.config import ModelConfig
    from vllm_tgis_adapter_tpu.engine.weights import (
        CheckpointIndex,
        load_model_params,
    )
    from vllm_tgis_adapter_tpu.models import get_model_class

    path, hf, family = written
    mcfg = ModelConfig.from_pretrained(str(path), dtype="float32")
    params = load_model_params(mcfg, str(path))
    model = get_model_class(mcfg.model_type)(mcfg)
    want = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, params) == jax.tree.map(
        lambda a: a.shape, want)
    top, layers = family.tensors(hf)
    on_disk = set(CheckpointIndex(str(path)).remaining())
    assert on_disk == {n for table in (top, *layers) for n in table}
