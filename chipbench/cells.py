"""The benchmark's data, found by name: configurations, cells, per-layer
metric readers, the kernels' layers and the chips' peaks."""
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_config(name, root=HERE):
    return _json(Path(root) / "configs" / f"{name}.json")


def load_cell(name, root=HERE):
    """(cell, config) of the cell ``name``."""
    cell = _json(Path(root) / "workloads" / f"{name}.json")
    cell["name"] = name
    return cell, load_config(cell["config"], root)


def load_benchmark(checkout=CHECKOUT):
    path = Path(checkout) / "BENCHMARK.json"
    return _json(path) if path.exists() else None


def metric_entries(bench, cell_name, trace: bool):
    """The metrics (name, unit) a run of ``cell_name`` reports: the
    end-to-end ones, or with a trace the per-layer ones."""
    key = "per_layer" if trace else "end_to_end"
    return [(m["name"], m["unit"]) for m in bench[key]
            if cell_name in m.get("workloads", [cell_name])]


def read_metric(name, run, root=HERE):
    path = Path(root) / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def layers(kind, root=HERE):
    """{name: layer} from ``<kind>/<name>.json``."""
    return {p.stem: _json(p)["layer"]
            for p in sorted((Path(root) / kind).glob("*.json"))}


def kernel_layers(root=HERE):
    """{kernel name: layer} from ``kernels/<kernel>.json``."""
    return layers("kernels", root)


def peaks(device_kind, root=HERE):
    table = _json(Path(root) / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json has {sorted(table)}")
    return table[device_kind]
