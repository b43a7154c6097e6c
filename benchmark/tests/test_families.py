"""A block family is one module found by name: ``families/<family>.py``.
The reference, the byte counts and ``run.py`` reach it only through
``families.load`` / ``families.require``, so adding a family is adding a
file; and moving the first two families into files moved no number."""
import importlib
import json
import sys
import types
from pathlib import Path

import pytest

import families

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH.parent))
B = importlib.import_module("bytes")          # benchmark/bytes.py
M = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CONFIGS = sorted(p.stem for p in (BENCH / "configs").glob("*.json"))
FAMILIES = sorted(p.stem for p in (BENCH / "families").glob("*.py")
                  if p.stem != "__init__")
PINS = json.loads((BENCH / "tests" / "data" / "family_pins.json").read_text())
# the name of a family nobody has added: not a name a model could bring
# (PR 27 used one that PR 28 then added a file for)
NOBODY = "never_a_family"


def conf(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def toy_family():
    """A family the benchmark has never known: a layer adds its one leaf
    ``w`` to every row, no norm."""
    mod = types.ModuleType("families.toy")

    def equations(mc):
        import jax.numpy as jnp

        def embed(params, ids):
            return params.embed["tokens"][ids].astype(jnp.float32)

        return embed, (lambda p, x: x + p["w"]), (lambda params, x: x)

    mod.equations = equations
    mod.layer_matrix_elements = lambda mc: 7 * mc["hidden_size"]
    mod.layer_scale_elements = lambda mc: 5
    return mod


TOY = {"family": "toy", "vocab_size": 11, "hidden_size": 4, "num_layers": 3,
       "num_heads": 2, "num_kv_heads": 1, "intermediate_size": 8}


def test_an_injected_family_is_what_the_reference_runs(monkeypatch):
    import jax.numpy as jnp
    import numpy as np

    import reference
    monkeypatch.setitem(sys.modules, "families.toy", toy_family())
    rng = np.random.default_rng(0)
    tokens = rng.normal(size=(11, 4)).astype(np.float32)
    w = rng.normal(size=(3, 4)).astype(np.float32)
    head = rng.normal(size=(4, 11)).astype(np.float32)
    params = types.SimpleNamespace(
        embed={"tokens": jnp.asarray(tokens)}, layers={"w": jnp.asarray(w)},
        final_norm={}, lm_head={"w": jnp.asarray(head)})
    ids, n_prompt = [3, 1, 4, 1, 5, 9, 2, 6], 5
    got = reference.emitted_logprobs(params, TOY, ids, n_prompt)
    x = tokens[ids].astype(np.float64) + w.astype(np.float64).sum(0)
    logits = x[n_prompt - 1: len(ids) - 1] @ head.astype(np.float64)
    lp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    assert got["logprobs"] == pytest.approx(
        [lp[i, t] for i, t in enumerate(ids[n_prompt:])], abs=1e-5)
    assert got["best_ids"] == [int(r.argmax()) for r in lp]


def test_an_injected_family_is_what_the_byte_counts_use(monkeypatch):
    toy = toy_family()
    monkeypatch.setitem(sys.modules, "families.toy", toy)
    assert B.layer_matrix_elements(TOY) == 28
    assert B.layer_scale_elements(TOY) == 5
    head = 11 * 4 * 2
    assert B.weight_bytes_per_pass(TOY, "int8") == 3 * (28 + 5 * 4) + head
    assert B.weight_bytes_per_pass(TOY, "none", 2) == 3 * 28 * 2 / 2 + head / 2
    # no kv_bytes_per_token of its own: layers x 2 x kv heads x head size
    assert B.kv_bytes_per_token(TOY) == 3 * 2 * 1 * 2 * 2
    toy.kv_bytes_per_token = lambda mc, kv_bytes, chips: 3 * kv_bytes / chips
    assert B.kv_bytes_per_token(TOY, 2, 2) == 3
    assert B.kv_read_bytes_per_step(TOY, 10) == 60


def test_an_unknown_family_names_the_file_to_add():
    import reference
    unknown = dict(TOY, family=NOBODY)
    for ask in (lambda: families.load(NOBODY),
                lambda: B.layer_matrix_elements(unknown),
                lambda: B.weight_bytes_per_pass(unknown, "int8"),
                lambda: B.kv_bytes_per_token(unknown),
                lambda: reference.emitted_logprobs(None, unknown, [1, 2], 1)):
        with pytest.raises(families.UnknownFamily,
                           match=rf"add benchmark/families/{NOBODY}\.py"):
            ask()
    with pytest.raises(families.UnknownFamily, match=r"families/a\.b\.py"):
        families.require("a.b")


@pytest.mark.parametrize("block", ["published", "rehearsal"])
def test_run_py_refuses_an_unknown_family_before_any_child(
        block, tmp_path, monkeypatch):
    import run as bench_run
    entry = M["configs"][0]
    c = json.loads((BENCH.parent / entry["file"]).read_text())
    (c if block == "published" else c["rehearsal"])["model_config"][
        "family"] = NOBODY
    root = tmp_path / "checkout"
    (root / Path(entry["file"]).parent).mkdir(parents=True)
    (root / entry["file"]).write_text(json.dumps(c))
    (root / "BENCHMARK.json").write_text(json.dumps(M))
    (root / "distributed_inference_demo_tpu").mkdir()
    (root / "distributed_inference_demo_tpu" / "cli.py").touch()
    monkeypatch.setattr(bench_run, "ROOT", root)
    started = []
    monkeypatch.setattr(bench_run, "Stack",
                        lambda *a, **k: started.append(a))
    cell = next(w["name"] for w in M["workloads"]
                if w["config"] == entry["name"])
    with pytest.raises(bench_run.BenchFailure,
                       match=rf"add benchmark/families/{NOBODY}\.py"):
        bench_run.load_cell(cell)
    assert bench_run.main(["--workload", cell, "--seconds", "1",
                           "--rehearse-cpu"]) == 1
    assert not started


def test_only_a_family_s_own_file_holds_its_name():
    """``run.py``, ``replica_main.py``, ``reference.py``, ``bytes.py``,
    ``stack.py``, ``find_knee.py``, every reader and every other module of
    the yardstick: none holds a family's name, in any case of letters, so
    none needs an edit for a new one."""
    assert {"qwen2", "bloom"} <= set(FAMILIES)
    files = [p for p in BENCH.rglob("*.py")
             if not {"tests", "out"} & set(p.relative_to(BENCH).parts)
             and p.parent.name != "families"]       # out/ is not in git
    files.append(BENCH / "families" / "__init__.py")
    assert {"run.py", "replica_main.py", "reference.py", "bytes.py",
            "stack.py", "find_knee.py"} <= {p.name for p in files}
    assert any(p.parent.name == "layer_metrics" for p in files)
    assert any(p.parent.name == "end_to_end" for p in files)
    held = [(p.name, n) for p in files for n in FAMILIES
            if n.lower() in p.read_text().lower()]
    assert not held


def test_every_family_file_serves_a_configuration_and_every_one_has_its():
    """No family module that no configuration of ``BENCHMARK.json`` uses,
    and no configuration (or rehearsal model) without its family's file."""
    used = set()
    for c in map(conf, CONFIGS):
        for served in (c, c["rehearsal"]):
            family = served["model_config"]["family"]
            assert (BENCH / "families" / f"{family}.py").is_file()
            families.require(family)
            used.add(family)
    assert used == set(FAMILIES)


def test_a_family_with_a_replay_brings_its_own_test_file():
    """``tests/test_reference.py`` compares with the program's one causal
    forward and leaves out a family that generates otherwise (one with a
    ``replay``): its comparison with the program is its own file's."""
    for family in FAMILIES:
        if hasattr(families.load(family), "replay"):
            assert (BENCH / "tests" / f"test_{family}_family.py").is_file()
    # the seven configurations PR 51 found are compared there still
    assert len(importlib.import_module("test_reference").LEFT_TO_RIGHT) >= 7


@pytest.mark.parametrize("name", sorted(PINS["reference"]))
def test_reference_equals_the_values_pinned_before_the_families_moved(name):
    """``tests/data/family_pins.json`` was written by the parent's tree
    (commit e427e29: one ``reference.py`` with both families inside it)
    on each configuration's rehearsal model, seed and ids as in
    ``test_reference_agrees_with_the_program_at_toy_size``.  Equal to the
    last float32 digit: the same operations in the same order.  (PR 26's
    and PR 27's sandboxes gave the same file byte for byte; a CPU whose
    vector width orders a float32 sum otherwise would differ in the last
    digits on the parent's tree too.)  Only the configurations that
    existed then are pinned; a later one's family has its own test file."""
    import jax

    import reference
    from distributed_inference_demo_tpu.models.base import ModelConfig
    from distributed_inference_demo_tpu.models.decoder import init_full_params

    toy = conf(name)["rehearsal"]
    fields = toy["model_config"]
    quant = "int8" if toy["serve_model"].endswith("-int8") else "none"
    cfg = ModelConfig(**fields, quantization=quant)
    params = init_full_params(jax.random.PRNGKey(3), cfg, quantize=True)
    ids = [(7 * i + 3) % cfg.vocab_size for i in range(40)]
    assert reference.emitted_logprobs(params, fields, ids, 24) \
        == PINS["reference"][name]


@pytest.mark.parametrize("name", sorted(PINS["bytes"]))
def test_byte_counts_equal_the_parent_s_integers(name):
    """The published configurations' counts, as the parent's ``bytes.py``
    (its own ``if family`` branches) gave them."""
    mc, want = conf(name)["model_config"], PINS["bytes"][name]
    assert B.layer_matrix_elements(mc) == want["layer_matrix_elements"]
    assert B.layer_scale_elements(mc) == want["layer_scale_elements"]
    assert {f"{q}.{c}": B.weight_bytes_per_pass(mc, q, c)
            for q in ("none", "int8") for c in (1, 4)} \
        == want["weight_bytes_per_pass"]
    assert {f"{kb}.{c}": B.kv_bytes_per_token(mc, kb, c)
            for kb in (1, 2) for c in (1, 4)} == want["kv_bytes_per_token"]


def test_the_shape_arithmetic_imports_no_jax():
    """The benchmark's parent reads the byte counts and must stay off
    JAX (a process that has touched it can hold the chip): in a fresh
    interpreter, ``run.py`` and every family's counts leave ``jax``
    unimported."""
    import subprocess
    code = (
        "import sys, json, importlib, pathlib\n"
        f"sys.path.insert(0, {str(BENCH)!r})\n"
        "import run, families\n"
        "B = importlib.import_module('bytes')\n"
        f"for p in pathlib.Path({str(BENCH / 'configs')!r}).glob('*.json'):\n"
        "    mc = json.loads(p.read_text())['model_config']\n"
        "    B.weight_bytes_per_pass(mc, 'int8'); B.kv_bytes_per_token(mc)\n"
        f"for f in {FAMILIES!r}:\n"
        "    families.load(f)\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == 0, done.stderr
