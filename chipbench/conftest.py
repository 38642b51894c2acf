"""Fixtures of the benchmark's CPU tests: a copy of the benchmark whose
configurations are cut to a size the Pallas interpreter runs in a
second.  The tests never take a chip."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import pytest  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"psia-t1": {"n_tasks": 64, "cloud_n": 256, "P": 4},
        "mandelbrot-t1": {"side": 128, "n_tasks": 4, "max_iters": 32}}


# cells whose files the benchmark holds, added to a copy's BENCHMARK.json
# where it does not list them (the way a later change adds a cell)
CELLS = {
    "psia-fac-1survivor": ("psia-t1", "fac-1survivor"),
    "mandelbrot-ss": ("mandelbrot-t1", "ss"),
    "psia-fac": ("psia-t1", "fac"),
    "mandelbrot-fac-slow1": ("mandelbrot-t1", "fac-slow1"),
}
LISTS = {"loop_s_p95": ["mandelbrot-ss"],
         "spin_image_roofline": ["psia-fac-1survivor", "psia-fac"],
         "mandelbrot_roofline": ["mandelbrot-ss", "mandelbrot-fac-slow1"]}


def copy_bench(dst: Path, *, every_cell: bool = False) -> Path:
    """BENCHMARK.json and the benchmark's directory, copied to ``dst``;
    with ``every_cell`` the copy's BENCHMARK.json lists all of ``CELLS``."""
    shutil.copytree(REPO / "chipbench", dst / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    m = json.loads((REPO / "BENCHMARK.json").read_text())
    if every_cell:
        configs = {c["name"] for c in m["configs"]}
        for cfg in sorted({c for c, _ in CELLS.values()} - configs):
            m["configs"].append({
                "name": cfg, "source": "https://arxiv.org/abs/1905.08073",
                "file": f"chipbench/configs/{cfg}.json", "reduced": [],
                "why": "test"})
        cells = {w["name"] for w in m["workloads"]}
        for name, (cfg, mix) in CELLS.items():
            if name not in cells:
                m["workloads"].append({"name": name, "config": cfg,
                                       "traffic": mix, "chips": 1,
                                       "why": "test"})
        metrics = {x["name"]: x for x in m["end_to_end"] + m["per_layer"]}
        for name, cells_of in LISTS.items():
            if name in metrics:
                metrics[name]["workloads"] = cells_of
            elif name == "loop_s_p95":
                m["end_to_end"].append({
                    "name": name, "unit": "s", "better": "lower",
                    "bound": 0.05, "source": "host_clock",
                    "workloads": cells_of})
            else:
                m["per_layer"].append({
                    "name": name, "unit": "%", "better": "higher",
                    "source": "device_trace", "layer": "kernels",
                    "moves": "loop_s", "workloads": cells_of})
    (dst / "BENCHMARK.json").write_text(json.dumps(m))
    return dst


@pytest.fixture
def bench_root(tmp_path) -> Path:
    """A copy of the benchmark at its own sizes, listing every cell."""
    return copy_bench(tmp_path, every_cell=True)


@pytest.fixture
def tiny_root(tmp_path) -> Path:
    root = copy_bench(tmp_path, every_cell=True)
    for name, sizes in TINY.items():
        f = root / "chipbench" / "configs" / f"{name}.json"
        cfg = json.loads(f.read_text())
        cfg.update(sizes)
        f.write_text(json.dumps(cfg))
    return root
