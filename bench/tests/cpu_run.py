"""Run a cell of the benchmark on the CPU at the test widths, as the
command would on the chip, and return its last line."""
import json

from bench import run
from bench.tests.rehearse import CPU_PEAKS, make_root


def run_cell(tmp_path, capsys, cell, trace=0, seconds=4, seed=2 ** 33 + 5,
             control=0, **root_kw):
    root = make_root(tmp_path, **root_kw)
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace), "--control",
                   str(control)], root=root, require_tpu=False,
                  peak_table=CPU_PEAKS)
    out, err = capsys.readouterr()
    assert rc == 0, err[-2000:]
    last = json.loads(out.strip().splitlines()[-1])
    return last, err, root
