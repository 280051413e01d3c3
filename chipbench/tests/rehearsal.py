"""A benchmark root in a temporary directory with the cells' files at a
size the CPU runs in seconds: the same harness, generator, reference and
check, on the program's reduced configurations, plus a cell of four
replicas (``REPLICAS``) sharing the CPU device."""
from __future__ import annotations

import copy
import json
import pathlib

from chipbench import harness

ROOT = harness.ROOT
REPLICAS = "replicas.x4"

SMALL = {
    "minicpm-2b": {"hidden_size": 64, "intermediate_size": 128,
                   "num_attention_heads": 4, "num_key_value_heads": 4,
                   "head_dim": 16, "num_hidden_layers": 2,
                   "vocab_size": 250,
                   "serve": {"max_batch": 4, "max_len": 128}},
    "mamba2-1.3b": {"d_model": 64, "n_layer": 2, "vocab_size": 250,
                    "d_state": 16, "headdim": 16, "chunk_size": 16,
                    "serve": {"max_batch": 4, "max_len": 128}},
}


def small_mix(mix):
    mix = copy.deepcopy(mix)
    for fam in mix["families"].values():
        fam["in_mean"], fam["in_std"] = fam["in_mean"] / 10, fam["in_std"] / 10
        fam["out_median"] = fam["out_median"] / 10
    mix.update(out_cap=16, prompt_bucket=16, prefix_tokens=8, lead_s=1,
               tail_s=20)
    return mix


def make_root(tmp: pathlib.Path, gap_mean=None) -> pathlib.Path:
    """Copy BENCHMARK.json and every file a cell names into ``tmp``,
    shrunk to the CPU size; returns ``tmp``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        spec = json.loads((ROOT / c["file"]).read_text())
        spec.update(SMALL[c["name"]])
        dest = tmp / c["file"]
        dest.parent.mkdir(parents=True, exist_ok=True)
        dest.write_text(json.dumps(spec))
    for kind in ("traffic", "cells"):
        (tmp / "chipbench" / kind).mkdir(parents=True, exist_ok=True)
    for w in bench["workloads"]:
        mix = json.loads((ROOT / "chipbench" / "traffic"
                          / f"{w['traffic']}.json").read_text())
        (tmp / "chipbench" / "traffic" / f"{w['traffic']}.json").write_text(
            json.dumps(small_mix(mix)))
        cell = json.loads((ROOT / "chipbench" / "cells"
                           / f"{w['name']}.json").read_text())
        cell.update(rate_wps=1.0, solo={"ttft_base_s": 0.05,
                                        "ttft_per_token_s": 1e-4,
                                        "tpot_s": 0.02})
        cell["check"].update(sample_tokens=64, sample_max=6)
        if gap_mean is not None:
            cell["check"]["gap_mean"] = gap_mean
        (tmp / "chipbench" / "cells" / f"{w['name']}.json").write_text(
            json.dumps(cell))
    # four replicas of the first cell behind the router: the harness's
    # path across engines, which no committed cell takes yet
    first = bench["workloads"][0]
    bench["workloads"].append(dict(first, name=REPLICAS, chips=4))
    cells = tmp / "chipbench" / "cells"
    (cells / f"{REPLICAS}.json").write_text(
        (cells / f"{first['name']}.json").read_text())
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
