"""Benchmark harness: one module per paper table/figure (+ framework
extras).  Prints ``name,us_per_call,derived`` CSV."""

import sys


def main() -> None:
    from repro import compile_cache
    compile_cache.enable()
    from benchmarks import (fig7_sssp, fig8_bfs, fig9_tradeoffs, fig10_ns,
                            fig11_chunking, fig12_adaptive, fig13_fused,
                            fig14_operators, fig15_sharded, fig16_pallas,
                            fig17_delta, fig18_serving, table2_graphs,
                            moe_balance, lm_step)
    modules = [
        ("table2_graphs", table2_graphs),
        ("fig7_sssp", fig7_sssp),
        ("fig8_bfs", fig8_bfs),
        ("fig9_tradeoffs", fig9_tradeoffs),
        ("fig10_ns", fig10_ns),
        ("fig11_chunking", fig11_chunking),
        ("fig12_adaptive", fig12_adaptive),
        ("fig13_fused", fig13_fused),
        ("fig14_operators", fig14_operators),
        ("fig15_sharded", fig15_sharded),
        ("fig16_pallas", fig16_pallas),
        ("fig17_delta", fig17_delta),
        ("fig18_serving", fig18_serving),
        ("moe_balance", moe_balance),
        ("lm_step", lm_step),
    ]
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    for name, mod in modules:
        if only and only != name:
            continue
        for line in mod.run(verbose=False):
            print(line, flush=True)


if __name__ == "__main__":
    main()
