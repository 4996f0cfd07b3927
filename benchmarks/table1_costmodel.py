"""Table 1/2 artifacts: SA classes + per-model characterization summary."""
from __future__ import annotations

from repro.costmodel import (DEFAULT_MAS, layer_cost)
from repro.costmodel.layers import conv2d, fc
from repro.launch.compile_cache import use_compile_cache
from repro.workloads import build_registry


def run() -> dict:
    out = {"sas": [], "models": {}}
    probe_layers = [conv2d("conv3x3_56", 56, 56, 128, 128, 3),
                    fc("fc4k", 4096, 4096)]
    for sa in DEFAULT_MAS.sas:
        row = {"name": sa.name, "dataflow": sa.dataflow,
               "peak_macs_per_cycle": sa.peak_macs_per_cycle}
        for layer in probe_layers:
            lat, bw, en = layer_cost(sa, layer)
            row[layer.name] = {"lat_us": round(lat, 2),
                               "bw_gbps": round(bw, 2),
                               "energy_uj": round(en, 2)}
        out["sas"].append(row)
        print(f"table1,{sa.name},{sa.dataflow},"
              f"{sa.peak_macs_per_cycle}macs/cyc", flush=True)
    reg = build_registry("mixed")
    d = reg.dense()
    for i, name in enumerate(reg.model_names):
        out["models"][name] = {
            "layers": int(d["n_layers"][i]),
            "min_lat_us": round(float(d["min_lat"][i]), 1),
        }
        print(f"table2,{name},layers={d['n_layers'][i]},"
              f"min_lat_us={d['min_lat'][i]:.1f}", flush=True)
    return out


def main():
    use_compile_cache()
    run()


if __name__ == "__main__":
    main()
