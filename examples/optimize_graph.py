"""Xenos graph-optimization walkthrough + d-Xenos distributed planning.

Shows the metadata-level rewrites on a hand-built graph: pattern
identification (Table 1), CBR fusion, operator linking (Figure 4/5), DOS
split plans (§4.2), and the d-Xenos partition-scheme search (Algorithm 1).

    PYTHONPATH=src python examples/optimize_graph.py
"""
import numpy as np

from repro.core import DeviceSpec, Graph, execute, init_params, pipeline
from repro.core import dos, patterns, planner
from repro.core import graph as G
from repro.launch.compile_cache import enable_compile_cache


def build_fig5_graph() -> Graph:
    """The paper's Figure-5 example: Conv1x1 -> Bn -> Bias -> Relu -> AvgPool."""
    g = Graph("fig5")
    x = g.add_input("fm", (1, 16, 16, 64))
    y = G.conv2d(g, x, 128, 1, name="conv1x1")
    y = G.bn(g, y)
    y = G.bias(g, y)
    y = G.relu(g, y)
    y = G.pool(g, y, "avg", 2)
    g.mark_output(y)
    return g


def main():
    g = build_fig5_graph()
    print(f"input graph: {[n.op_type for n in g.nodes]}")

    ident = patterns.identify(g)
    print(f"identified fusions: {[m.nodes for m in ident['fusions']]}")

    # the pass manager runs fuse_cbr -> link_operators -> dos_split, verifies
    # the graph after every rewrite, and reports what each pass did
    dev = DeviceSpec.tms320c6678()
    opt, report = pipeline.optimize(g, dev)
    print(f"after the pipeline (Fig 5a/5b, CBRA): "
          f"{[n.op_type for n in opt.nodes]}")
    cbra = next(n for n in opt.nodes if n.op_type == "cbra")
    print(f"  linked-op dataflow metadata: {cbra.dataflow}")
    for name, plan in dos.plans(opt).items():
        print(f"DOS plan for {name} (Fig 5d/e): fmap_parts={plan.fmap_parts} "
              f"param_chunks={plan.param_chunks} fits_l2={plan.fits_l2}")
    print(report.format())

    # equivalence
    params = init_params(g)
    x = {"fm": np.random.default_rng(0).normal(size=(1, 16, 16, 64)).astype("float32")}
    a = execute(g, params, x, mode="vanilla")
    b = execute(opt, params, x, mode="xenos")
    err = float(np.max(np.abs(np.asarray(a[0]) - np.asarray(b[0]))))
    print(f"optimized == original: max err {err:.2e}")
    assert err < 1e-4

    # d-Xenos planning (Algorithm 1 over the Figure-6 scheme set) as the
    # opt-in `dxenos_plan` pass: annotates compute ops with their best scheme
    planned, dreport = pipeline.optimize(
        g, passes=("dxenos_plan",), options={"n_devices": 4})
    print(f"\ndxenos_plan pass: {dreport.passes[0].summary}")
    best, best_t, all_t = planner.plan_distributed(g, n_devices=4)
    print("d-Xenos schemes (4 devices, modeled):")
    for k, v in sorted(all_t.items(), key=lambda kv: kv[1]):
        mark = " <= best" if k == str(best) else ""
        print(f"  {k:24s} {v * 1e6:9.1f} us{mark}")
    print("optimize_graph OK")


if __name__ == "__main__":
    enable_compile_cache()
    main()
