import json
import os
import pathlib
import shutil

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

# a cut of dcgan small enough for the CPU: every width times 1/32, as the
# program's ``channel_scale`` cuts it, and batch 4
SCALE = 0.03125


def tiny_config(name: str = "dcgan") -> dict:
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())

    def cut(c):
        return c if c <= 3 else max(1, int(c * SCALE))

    for role in ("generator", "discriminator"):
        for layer in cfg[role]:
            if layer["kind"] == "dense":
                layer["reshape"][-1] = cut(layer["reshape"][-1])
                layer["cout"] = (layer["reshape"][0] ** cfg["dims"]
                                 * layer["reshape"][-1])
            else:
                layer["cin"], layer["cout"] = cut(layer["cin"]), \
                    cut(layer["cout"])
    cfg.update(name="tiny", channel_scale=SCALE, batch=4)
    return cfg


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout holding the benchmark's files and one tiny configuration
    with two training cells: ``tiny.train``, which the metrics with a
    ``workloads`` list name, and ``tiny.other``, which none names."""
    for d in ("metrics", "traffic"):
        shutil.copytree(BENCH / d, tmp_path / "bench" / d)
    (tmp_path / "bench" / "configs").mkdir()
    (tmp_path / "bench" / "limits").mkdir()
    cfg = tiny_config()
    (tmp_path / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(cfg))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [{"name": name, "config": "tiny",
                           "traffic": "train", "chips": 1, "why": "test"}
                          for name in ("tiny.train", "tiny.other")]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = ["tiny.train"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for name in ("tiny.train", "tiny.other"):
        shutil.copy(BENCH / "limits" / "dcgan.train.json",
                    tmp_path / "bench" / "limits" / f"{name}.json")
    return tmp_path
