"""Benchmark harness: one cell of `BENCHMARK.json`, run once, from the
client side of `repro.launch.serve.build_engine`.

Everything that belongs to one configuration, traffic mix, cell or metric
lives in a file of its own, found by the name `BENCHMARK.json` gives it:

    bench/configs/<config>.json      sizes, serve flags, the cut
    bench/references/<name>.py       plain float32 reference + weight maker
    bench/traffic/<mix>.json         parameters of the one generator
    bench/cells/<cell>.json          the cell's correctness limits
    bench/metrics/<metric>.py        one reader per metric
"""
