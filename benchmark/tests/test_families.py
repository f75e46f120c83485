"""A model family is a file found by name.  ``families/llama.py`` writes
byte for byte what ``checkpoint.py`` and ``reference.py`` wrote and scored
before the family was a file (pinned from commit 79234a0, before anything
moved), and a family that is not llama needs no edit under ``benchmark/``:
the toy below lives under ``tmp_path`` alone."""

import asyncio
import hashlib
import json
import textwrap
from pathlib import Path

import pytest

import checkpoint
import run

BENCH = Path(__file__).resolve().parent.parent
DATA = Path(__file__).resolve().parent / "data"
CONFIGS = sorted(p.stem for p in (BENCH / "configs").glob("*.json"))
#: the configurations the parent had, each with its pinned listing
PINNED = sorted(p.name[len("tensors."):-len(".json")]
                for p in DATA.glob("tensors.*.json"))

#: sha256 of what the parent's ``write_checkpoint`` wrote for ``rehearsal``
#: at seed 5
REHEARSAL_SHA256 = {
    "COMPLETE":
        "bb852966437a035c3618b1dc6e14c39bc03e648075edc6ca7d2101ac373fc816",
    "config.json":
        "4adf63054ec1f971118ea096ff6d6475891601aa2550fe9abc6c297ac40ca790",
    "model-00001-of-00002.safetensors":
        "497caed166733e646041bae70571f362ddc0a288b447f90701ea1adf3523cace",
    "model-00002-of-00002.safetensors":
        "956ebc1359ef38d0922a87cdc2d31af1501ea299cb937b89c23592856ba1f9ba",
    "model.safetensors.index.json":
        "e3521209b5a6f2361cca96887222366f6cebdbe6332da6991d9654546c38c42f",
}
#: what the parent's ``reference.score`` gave on that checkpoint
SEQUENCES = [[3, 17, 99, 200, 41, 7, 300, 12, 5, 410, 77],
             [9, 8, 7, 6, 5, 4, 3, 2, 1, 0, 11, 12, 13, 500, 250, 125, 60, 30]]
PROMPT_LENS = [4, 10]
REHEARSAL_SCORES = [
    [-6.131977081298828, -6.390629768371582, -6.398433208465576,
     -6.226079940795898, -6.535066604614258, -6.057759761810303,
     -6.13078498840332],
    [-6.154970169067383, -6.006493091583252, -5.978732585906982,
     -6.12884521484375, -6.177240371704102, -6.060923099517822,
     -6.341759204864502, -6.2066426277160645],
]


def served_config(name: str) -> tuple[dict, dict]:
    """(the configuration file, the ``config.json`` it is served with)."""
    full = run.load("configs", name)
    return full, run.served_config(full)


def sha256(path: Path) -> str:
    return hashlib.sha256(path.read_bytes()).hexdigest()


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    full, hf = served_config("rehearsal")
    family = run.load_family(full["family"])
    path, reused = checkpoint.ensure_checkpoint(
        tmp_path_factory.mktemp("work"), "rehearsal", hf, 5, full["dtype"],
        family.tensors)
    assert not reused
    return path, hf, family


@pytest.mark.parametrize("file", sorted(REHEARSAL_SHA256))
def test_the_rehearsal_checkpoint_is_byte_for_byte_the_parents(rehearsal,
                                                               file):
    path, _, _ = rehearsal
    assert sha256(path / file) == REHEARSAL_SHA256[file]


def test_family_is_not_in_the_served_config(rehearsal):
    path, _, _ = rehearsal
    assert "family" not in json.loads((path / "config.json").read_text())
    assert "family" not in json.loads(
        (path / checkpoint.MARKER).read_text())["config"]


def test_llama_scores_what_the_parents_reference_scored(rehearsal):
    path, hf, family = rehearsal
    assert family.score(path, hf, SEQUENCES, PROMPT_LENS) == REHEARSAL_SCORES


def listing(table: dict) -> list:
    """A table as JSON holds it; a fill's function by its name."""
    return [[name, list(shape), [getattr(f, "__name__", f) for f in fill]]
            for name, (shape, fill) in table.items()]


@pytest.mark.parametrize("config", PINNED)
def test_a_configurations_table_is_the_parents_listing_in_order(config):
    """Every configuration the parent had names llama; its table (name,
    shape, fill) is what the parent's ``tensor_shapes`` listed, ones for
    one dimension and the pool at 0.02 for two, in the order written."""
    full, hf = served_config(config)
    assert full["family"] == "llama"
    top, layers = run.load_family(full["family"]).tensors(hf)
    pinned = json.loads((DATA / f"tensors.{config}.json").read_text())
    assert listing(top) == pinned["top"]
    assert [listing(layer) for layer in layers] == pinned["layers"]


@pytest.mark.parametrize("config", CONFIGS)
def test_every_configuration_names_a_family_that_fits_its_widths(config):
    """Holds for a configuration a later PR adds, whatever its family:
    the module is there, its table has a layer for every layer and a
    tensor with the vocabulary's rows, and every matrix has the residual
    width (or a low rank the configuration names) on one side.  Its copy
    in the tier-1 tests is ``tests/test_benchmark_families.py``."""
    full, hf = served_config(config)
    assert "family" in full and "family" not in hf
    top, layers = run.load_family(full["family"]).tensors(hf)
    assert top and len(layers) == hf["num_hidden_layers"] and all(layers)
    shapes = [shape for table in (top, *layers)
              for shape, _ in table.values()]
    assert any(shape[0] == hf["vocab_size"] for shape in shapes)
    # a low-rank projection's second factor (deepseek_v2's q_b_proj and
    # kv_b_proj) has its rank where other matrices have the residual width
    widths = {hf["hidden_size"], hf.get("q_lora_rank"),
              hf.get("kv_lora_rank")} - {None}
    for shape in shapes:
        if len(shape) == 2:
            assert widths & set(shape), shape


def test_an_unknown_family_ends_the_run_naming_the_missing_file():
    with pytest.raises(SystemExit) as stop:
        run.load_family("no-such-family")
    assert str(BENCH / "families" / "no-such-family.py") in str(stop.value)


TOY = textwrap.dedent('''
    """A family that is not llama: a bag of embeddings through one 3-D
    mixing tensor, with a decay that a function fills."""
    import numpy as np

    from reference import Weights, chosen_logprobs

    LOGPROB_TOLERANCE = 0.5  # the toy's own


    def decay(shape, rng):
        return -np.exp(rng.uniform(-1.0, 1.0, shape))


    def tensors(cfg):
        d, v = cfg["hidden_size"], cfg["vocab_size"]
        top = {"embed": ((v, d), ("pool", 0.5)),
               "head.weight": ((v, d), ("pool", 0.5))}
        layers = [{f"blocks.{i}.mix": ((d, 1, 4), ("pool", 0.3)),
                   f"blocks.{i}.decay": ((d,), ("values", decay)),
                   f"blocks.{i}.gain.weight": ((d,), ("ones",))}
                  for i in range(cfg["num_hidden_layers"])]
        return top, layers


    def score(model_dir, cfg, sequences, prompt_lens):
        w = Weights(model_dir)
        out = []
        for ids, n in zip(sequences, prompt_lens):
            x = np.cumsum(w.get("embed")[np.asarray(ids)], axis=0)
            for i in range(cfg["num_hidden_layers"]):
                mix = w.get(f"blocks.{i}.mix").sum(axis=(1, 2))
                x = x * np.exp(w.get(f"blocks.{i}.decay")) * (
                    1.0 + mix) * w.get(f"blocks.{i}.gain.weight")
            logits = x[n - 1:-1] @ w.get("head.weight").T
            out.append(chosen_logprobs(logits, ids[n:]))
        return out
''')


def test_a_family_that_is_not_llama_is_a_file_and_nothing_else(
        tmp_path, monkeypatch):
    """``ensure_checkpoint`` and the reference child, through ``run.py``'s
    own lookup, on a family written under ``tmp_path``: a tensor named
    without ``.weight``, a 3-D tensor, a 1-D tensor filled by a function,
    pool stds other than 0.02, its own ``score`` and tolerance."""
    import numpy as np
    from safetensors import safe_open

    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "toy.py").write_text(TOY)
    monkeypatch.syspath_prepend(str(tmp_path))
    monkeypatch.setenv("PYTHONPATH", str(tmp_path))  # the reference child's
    before = {p: p.stat().st_mtime_ns for p in BENCH.rglob("*")
              if "__pycache__" not in p.parts and ".work" not in p.parts}

    family = run.load_family("toy")
    assert family.LOGPROB_TOLERANCE == 0.5
    cfg = {"vocab_size": 300, "hidden_size": 8, "num_hidden_layers": 5}
    path, reused = checkpoint.ensure_checkpoint(
        tmp_path / "work", "toy", cfg, 7, "float32", family.tensors)
    assert not reused
    assert checkpoint.ensure_checkpoint(
        tmp_path / "work", "toy", cfg, 7, "float32", family.tensors
    ) == (path, True)

    index = json.loads(
        (path / "model.safetensors.index.json").read_text())["weight_map"]
    top, layers = family.tensors(cfg)
    assert set(index) == set(top) | {n for layer in layers for n in layer}
    # five layers are two shards of layers and one of the rest
    assert len(set(index.values())) == 3

    def read(name):
        with safe_open(str(path / index[name]), framework="np") as f:
            return f.get_tensor(name)

    mix = np.concatenate([read(f"blocks.{i}.mix").ravel() for i in range(5)])
    assert read("blocks.0.mix").shape == (8, 1, 4)
    assert 0.2 < mix.std() < 0.4 and 0.4 < read("embed").std() < 0.6
    decays = read("blocks.3.decay")
    assert decays.shape == (8,) and (decays < 0).all()
    assert len(set(decays.tolist())) == 8  # not ones, not one value
    assert (read("blocks.4.gain.weight") == 1).all()

    sequences, prompt_lens = [[5, 9, 200, 31, 7, 150], [1, 2, 3, 299]], [3, 2]

    async def through_the_child():
        child = await run.start_reference(
            tmp_path / "job.json", "toy", path, cfg, sequences, prompt_lens)
        out, err = await child.communicate()
        assert child.returncode == 0, err.decode()[-2000:]
        return json.loads(out.decode().strip().splitlines()[-1])

    scored = asyncio.run(through_the_child())
    assert scored == family.score(path, cfg, sequences, prompt_lens)
    assert [len(s) for s in scored] == [3, 2]
    served = [[x + 0.4 for x in row] for row in scored]
    verdict = run.judge(served, scored, family.LOGPROB_TOLERANCE)
    assert verdict["ok"] and verdict["tolerance"] == 0.5
    assert verdict["tokens_scored"] == 5
    assert verdict["reference_logprob_spread"] == pytest.approx(
        max(map(max, scored)) - min(map(min, scored)))
    assert not run.judge([[x + 0.6 for x in row] for row in scored], scored,
                         family.LOGPROB_TOLERANCE)["ok"]

    after = {p: p.stat().st_mtime_ns for p in BENCH.rglob("*")
             if "__pycache__" not in p.parts and ".work" not in p.parts}
    assert after == before  # no file under benchmark/ made or touched
