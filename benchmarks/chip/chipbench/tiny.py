"""A cell at a size a CPU test run can hold: the smoke MMDiT (128 wide, 4
heads of 32, fp32) under a two-shape image/clip mix, judged by the limits
of the 1.3B mix cell.  Used by
the tests to drive the harness without a chip."""

from __future__ import annotations

import copy
import json

from .catalog import BENCH_DIR, Cell, load_family


def tiny_config() -> dict:
    config = json.loads((BENCH_DIR / "configs" / "wan2.1-1.3b.json").read_text())
    config.update(
        program={"config": "repro.configs.wan2_1_mmdit:smoke_config", "arch": "wan2.1-1.3b"},
        dim=128, ffn_dim=256, num_heads=4, num_layers=2, text_len=16,
        param_dtype="float32",
    )
    return config


TRAFFIC = {
    "kind": "train",
    "media": [[1, 128, 128], [5, 128, 128]],
    "weights": [0.5, 0.5],
    "launcher": ["--adaptive", "--batch", "1", "--seq", "2048"],
}


def tiny_cell(per_layer=()) -> Cell:
    return Cell(
        name="tiny", chips=1, config_name="wan2.1-1.3b", config=tiny_config(),
        family=load_family("wan"), traffic_name="tiny", traffic=copy.deepcopy(TRAFFIC),
        end_to_end=[
            {"name": "tokens_per_s", "unit": "tokens/s"},
            {"name": "mfu", "unit": "%"},
            {"name": "setup_s", "unit": "s"},
        ],
        per_layer=list(per_layer),
        limits=json.loads((BENCH_DIR / "limits" / "wan13b.mix.json").read_text()),
        window={"steps": 2, "seconds": 1.0},
    )
