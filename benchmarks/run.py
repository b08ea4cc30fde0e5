"""Benchmark aggregator: one module per paper table/figure.

Prints ``name,value,derived`` CSV.  The figure benchmarks are
deterministic models/simulations of the paper's switch; ``roofline``
reads the dry-run artifacts if present.  None of them times anything:
speed is measured on the chip by ``bench/run.py``.

Run: ``PYTHONPATH=src python -m benchmarks.run``
"""
import sys
import time


def main() -> None:
    from benchmarks import (fig07_single_buffer, fig10_aggregation,
                            fig11_switch_bw, fig13_sparse_model,
                            fig14_sparse_sim, fig15_network, roofline)
    modules = [fig07_single_buffer, fig10_aggregation, fig11_switch_bw,
               fig13_sparse_model, fig14_sparse_sim, fig15_network,
               roofline]
    print("name,value,derived")
    for mod in modules:
        t0 = time.time()
        try:
            rows = mod.run()
        except Exception as e:                       # pragma: no cover
            print(f"{mod.__name__}.ERROR,0,{e!r}")
            continue
        for name, val, derived in rows:
            print(f"{name},{val},{derived}")
        print(f"{mod.__name__}.elapsed_s,{time.time() - t0:.1f},",
              file=sys.stderr)


if __name__ == "__main__":
    main()
