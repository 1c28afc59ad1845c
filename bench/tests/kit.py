"""A tiny cell for the benchmark's CPU tests.

granite-8b's configuration file with the program's sizes overridden to a
smoke model, and a short Poisson mix, written as new files into a temporary
copy of the benchmark; nothing in the repository is edited.
"""

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 1, "d_head": 16, "d_ff": 128, "vocab_size": 256}


def tiny_config(**limits):
    c = json.loads((ROOT / "bench" / "configs" / "granite-8b.json").read_text())
    c.update(TINY, overrides={**c.get("overrides", {}), **TINY}, max_len=64)
    c["limits"].update(limits)
    return c


def copy_bench(dst: Path) -> Path:
    """A checkout of just the benchmark: ``BENCHMARK.json`` and ``bench/``."""
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", dst / "bench", ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return dst


def add_cell(root: Path, config: dict, mix: dict, name: str = "tiny") -> str:
    """New files and entries only: a configuration, a mix and a cell."""
    (root / "bench" / "configs" / f"{name}.json").write_text(json.dumps(config))
    (root / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(
        {"name": name, "source": "smoke", "file": f"bench/configs/{name}.json", "reduced": [], "why": "CPU test"}
    )
    cell = f"{name}.{name}"
    bench["workloads"].append({"name": cell, "config": name, "traffic": name, "chips": 1, "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return cell


def tiny_mix(**kw):
    mix = json.loads((ROOT / "bench" / "traffic" / "complete.json").read_text())
    mix.update(
        arrival={"kind": "poisson", "rate_rps": 1.0}, ramp_s=0.5, prompt_pool=[8, 12, 16, 20],
        output={"median": 6, "sigma": 0.5, "clip": [3, 10]}, slots=4, grace_s=60.0, check_tokens=40,
    )
    mix.update(kw)
    return mix
