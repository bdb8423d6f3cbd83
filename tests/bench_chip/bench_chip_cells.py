"""CPU-sized cells, written into a checkout-shaped directory, for the
benchmark's CPU tests."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"

TINY_LM = {
    "name": "tiny-lm", "arch": "qwen2_5_3b", "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 2, "vocab_size": 512, "rms_norm_eps": 1e-6,
    "rope_theta": 1e6, "tie_word_embeddings": True, "torch_dtype": "bfloat16",
    "backend": "inproc",
}


TAG_TRAFFIC = {"kind": "tag_round", "trainers": 3, "samples": [1, 40],
               "sample_picks": 2}


def write_root(root: Path, cells: dict) -> Path:
    """A checkout-shaped ``root`` with the given cells. ``cells`` maps a
    workload name to (config dict, traffic dict, limits dict, chips)."""
    bench = root / "benchmarks" / "chip"
    for sub in ("configs", "traffic", "limits"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    shutil.copytree(BENCH / "metrics", bench / "metrics", dirs_exist_ok=True)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"], spec["workloads"] = [], []
    for name, (cfg, traffic, limits, chips) in cells.items():
        cfg_file = f"benchmarks/chip/configs/{cfg['name']}.json"
        (root / cfg_file).write_text(json.dumps(cfg))
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(traffic))
        (bench / "limits" / f"{name}.json").write_text(json.dumps({"numbers": limits}))
        if cfg["name"] not in {c["name"] for c in spec["configs"]}:
            spec["configs"].append({"name": cfg["name"], "source": "test",
                                    "file": cfg_file, "reduced": [], "why": "test"})
        spec["workloads"].append({"name": name, "config": cfg["name"],
                                  "traffic": name, "chips": chips, "why": "test"})
    names = set(cells)
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            kinds = {w.split(".")[0] for w in m["workloads"]}
            m["workloads"] = sorted(n for n in names if n.split(".")[0] in kinds)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def real_limits(workload: str) -> dict:
    return json.loads((BENCH / "limits" / f"{workload}.json").read_text())["numbers"]
