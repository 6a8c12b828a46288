"""A new cell is data: a traffic file and `BENCHMARK.json` entries, read
by the harness as it stands."""
import json
import shutil
from pathlib import Path

import cell_small

ROOT = Path(__file__).resolve().parents[2]


def test_new_traffic_file_and_entry_make_a_cell(tmp_path):
    import jax

    import harness
    import run
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    mix = json.loads((ROOT / "bench/traffic/hist.json").read_text())
    mix.update(cell_small.SMALL["train"], num_trees=3)
    mix["tree_params"] = dict(mix["tree_params"], max_depth=5, num_bins=64)
    (tmp_path / "bench/traffic/hist.shallow.json").write_text(
        json.dumps(mix))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "higgs.hist.shallow",
                              "config": "higgs", "traffic": "hist.shallow",
                              "chips": 1, "why": "a test cell"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "higgs.hist" in m.get("workloads", []):
            m["workloads"].append("higgs.hist.shallow")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell("higgs.hist.shallow", root=tmp_path)
    line = run.run_cell("higgs.hist.shallow", 7, 0.2, 0,
                        devices=jax.devices()[:1], cell=cell)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"tree_rows_per_s", "setup_s"}
