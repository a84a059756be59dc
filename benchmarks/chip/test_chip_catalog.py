"""Cells, configurations, model families, traffic and per-layer metrics are
found by name from files; adding a cell, a metric or a configuration of a
new family edits no file; the command refuses a platform without a TPU."""

from __future__ import annotations

import importlib.util
import json
import shutil
import types

import pytest

from chipbench import catalog

BENCH = catalog.load_benchmark()


def _load_run():
    spec = importlib.util.spec_from_file_location("chipbench_run", catalog.BENCH_DIR / "run.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_is_found_with_its_files(name):
    cell = catalog.find_cell(name)
    assert cell.traffic["kind"] == "train"
    assert cell.config["name"] == cell.config_name
    assert cell.dims.layers == cell.config["num_layers"] < cell.config["published"]["num_layers"]
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "tokens_per_s"}
    assert cell.per_layer, "every cell reports a per-layer metric"


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_every_metric_has_a_reader_that_reads_nothing_from_nothing(name):
    read = catalog.metric_reader(name)
    empty = {"trace": None, "memory": [], "traced_microbatches": []}
    assert read(empty) is None


def test_config_files_match_benchmark_entries():
    for entry in BENCH["configs"]:
        config = json.loads((catalog.ROOT / entry["file"]).read_text())
        assert config["name"] == entry["name"]
        assert config["source"] == entry["source"]
        assert config["reduced"] == entry["reduced"]
        for key in entry["reduced"]:
            assert config[key] != config["published"][key]


def test_adding_a_cell_and_a_metric_edits_no_file(tmp_path):
    bench_rel = BENCH["paths"][0]
    shutil.copytree(catalog.BENCH_DIR, tmp_path / bench_rel,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / bench_rel).rglob("*") if p.is_file()}
    bench = json.loads(json.dumps(BENCH))
    # new files: a traffic mix, the new cell's limits and window, a metric reader
    (tmp_path / bench_rel / "traffic" / "clips.json").write_text(json.dumps({
        "kind": "train", "media": [[17, 480, 832]], "weights": [1.0],
        "launcher": ["--adaptive", "--batch", "8", "--seq", "512"],
    }))
    (tmp_path / bench_rel / "limits" / "wan13b.clips.json").write_text(
        (tmp_path / bench_rel / "limits" / "wan13b.mix.json").read_text()
    )
    (tmp_path / bench_rel / "windows" / "wan13b.clips.json").write_text(
        json.dumps({"steps": 20, "seconds": 30})
    )
    (tmp_path / bench_rel / "metrics" / "steps_seen.py").write_text(
        "def read(run):\n    return float(run['steps'])\n"
    )
    # new entries in BENCHMARK.json
    bench["workloads"].append({"name": "wan13b.clips", "config": "wan2.1-1.3b",
                               "traffic": "clips", "chips": 1, "why": "clips only"})
    bench["per_layer"].append({"name": "steps_seen", "unit": "steps", "better": "higher",
                               "source": "program_counter", "layer": "loop + engines",
                               "moves": "tokens_per_s", "workloads": ["wan13b.clips"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = catalog.find_cell("wan13b.clips", tmp_path)
    assert cell.traffic["media"] == [[17, 480, 832]]
    assert cell.window == {"steps": 20, "seconds": 30}
    assert cell.config_name == "wan2.1-1.3b"
    assert "steps_seen" in [m["name"] for m in cell.per_layer]
    assert "steps_seen" not in [m["name"] for m in catalog.find_cell("wan13b.mix", tmp_path).per_layer]
    read = catalog.metric_reader("steps_seen", tmp_path / bench_rel)
    assert read({"steps": 7}) == 7.0
    for path, data in before.items():
        assert path.read_bytes() == data, f"{path} changed"


STUB_FAMILY = '''"""A stand-in family: one width, two FLOPs a token."""
import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    layers: int


def dims_of(config):
    return Dims(d=config["hidden_size"], layers=config["num_layers"])


def model_flops(dims, b, s):
    return 2 * b * s * dims.d * dims.layers
'''


def test_adding_a_configuration_of_a_new_family_edits_no_file(tmp_path):
    bench_rel = BENCH["paths"][0]
    bench_dir = tmp_path / bench_rel
    shutil.copytree(catalog.BENCH_DIR, bench_dir, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    bench = json.loads(json.dumps(BENCH))
    # new files: the family, its configuration, a traffic mix, the cell's
    # limits and window
    (bench_dir / "chipbench" / "families" / "stub.py").write_text(STUB_FAMILY)
    (bench_dir / "configs" / "stub-1b.json").write_text(json.dumps({
        "name": "stub-1b", "source": "https://example.org/stub-1b", "family": "stub",
        "hidden_size": 3072, "num_layers": 2, "published": {"num_layers": 60},
        "reduced": ["num_layers"],
    }))
    (bench_dir / "traffic" / "clips.json").write_text(json.dumps({
        "kind": "train", "media": [[33, 480, 832]], "weights": [1.0],
        "launcher": ["--adaptive", "--batch", "8", "--seq", "512"],
    }))
    (bench_dir / "limits" / "stub.clips.json").write_text(
        json.dumps({"loss_gap": 1e-3, "grad_gap": 1e-3, "change_gap": 1e-3})
    )
    (bench_dir / "windows" / "stub.clips.json").write_text(json.dumps({"steps": 10, "seconds": 30}))
    # new entries in BENCHMARK.json
    bench["configs"].append({"name": "stub-1b", "source": "https://example.org/stub-1b",
                             "file": f"{bench_rel}/configs/stub-1b.json",
                             "reduced": ["num_layers"], "why": "a family of its own"})
    bench["workloads"].append({"name": "stub.clips", "config": "stub-1b", "traffic": "clips",
                               "chips": 1, "why": "clips only"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = catalog.find_cell("stub.clips", tmp_path)
    assert cell.family.__doc__.startswith("A stand-in family")
    assert (cell.dims.d, cell.dims.layers) == (3072, 2)
    assert "step_mfu" in [m["name"] for m in cell.per_layer]
    # the readers reach the counts through the cell's family
    trace = types.SimpleNamespace(window_s=1.0)
    record = {"trace": trace, "family": cell.family, "dims": cell.dims, "chips": 1,
              "peaks": {"bf16_flops_per_s": 1e12}, "traced_microbatches": [(1, 1000)]}
    assert catalog.metric_reader("step_mfu", bench_dir)(record) == pytest.approx(
        100.0 * 2 * 1000 * 3072 * 2 / 1e12
    )
    assert catalog.find_cell("wan13b.mix", tmp_path).family.__name__ == "chipbench_family_wan"
    for path, data in before.items():
        assert path.read_bytes() == data, f"{path} changed"


def test_unknown_cell_and_metric_are_errors():
    with pytest.raises(KeyError, match="no workload"):
        catalog.find_cell("no.such.cell")
    with pytest.raises(KeyError, match="no reader"):
        catalog.metric_reader("no_such_metric")


def test_command_refuses_a_platform_without_tpu(capsys):
    import jax

    assert jax.devices()[0].platform != "tpu"
    run = _load_run()
    with pytest.raises(SystemExit) as exit_info:
        run.main(["--workload", "wan13b.mix", "--seed", "1", "--seconds", "1"])
    assert exit_info.value.code != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_has_a_fixed_window(name):
    from chipbench.harness import window_steps

    cell = catalog.find_cell(name)
    assert cell.window["seconds"] == BENCH["run_seconds"]
    assert window_steps(cell, BENCH["run_seconds"]) == cell.window["steps"] >= 10
    assert window_steps(cell, 1) >= 1


@pytest.mark.parametrize("change", [
    {"chips": 4},
    {"launcher": ["--workers", "4", "--mesh", "--dispatch", "lpt"]},
    {"launcher": ["--dispatch", "knapsack"]},
    {"launcher": ["--batch", "8"], "adaptive": False},
], ids=["four_chips", "mesh", "dispatch", "not_adaptive"])
def test_a_cell_the_harness_would_not_run_as_described_is_refused(change, capsys):
    from chipbench.harness import refuse_unsupported

    cell = catalog.find_cell("wan13b.mix")
    if "chips" in change:
        cell.chips = change["chips"]
    else:
        extra = [] if change.get("adaptive") is False else ["--adaptive"]
        cell.traffic["launcher"] = extra + ["--batch", "8", "--seq", "512", *change["launcher"]]
    with pytest.raises(ValueError):
        refuse_unsupported(cell)
    refuse_unsupported(catalog.find_cell("wan13b.mix"))


def test_command_refuses_a_four_chip_cell_before_any_run(tmp_path, capsys):
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"][0]["chips"] = 4
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copytree(catalog.BENCH_DIR, tmp_path / BENCH["paths"][0],
                    ignore=shutil.ignore_patterns("__pycache__"))
    run = _load_run()
    run.ROOT = tmp_path
    with pytest.raises(SystemExit) as exit_info:
        run.main(["--workload", bench["workloads"][0]["name"], "--seed", "1"])
    assert exit_info.value.code == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "4 chips" in out.err
