"""Bring-up smoke run of CluSD serving on one TPU chip.

Drives the system through the same entry points a user calls
(`repro.launch.serve`, `build_index`, `train_selector`), in this one
process, at the paper's MS MARCO widths (`configs/clusd_msmarco.full()`:
dim 768, vocab 30,522, 4,096 postings per term, k_sparse 1,000, n = 32
candidates, LSTM hidden 32, m = 128 neighbours, max_selected 32, k_final
1,000) cut to one chip's share of the corpus: 2^20 documents in 1,024
clusters, so `cluster_cap` stays at the paper's 2,048-row block.

  Phase A  the corpus in HBM: `serve` builds the index, trains the
           selector and serves 64 queries through the engine's one-jit
           device path.
  Phase B  the README flow: `build_index` (format v2, PQ codes) ->
           `train_selector --publish --serve-check 8` (Pallas LSTM forward,
           hot selector reload, parity vs a fresh engine) -> `serve
           --index-dir --check-parity` (compiled ADC kernels).

Any phase that exits non-zero, or whose output lacks its OK line, fails
the run, as does an ADC or LSTM kernel that ran in no program as a Mosaic
kernel (`tpu_custom_call`). The last line of standard output is one JSON
object naming the device, printed only when every phase passed. With no
TPU the script exits non-zero before any phase.

    python chip_smoke.py
"""

import contextlib
import gc
import io
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# the smoke deployment: one chip's share of MS MARCO passage
N_DOCS = 1 << 20
N_CLUSTERS = 1024
N_QUERIES = 64
# serving batch: the in-memory device path gathers (batch, 32 x 2048, 768)
# float32 (192 MiB per query); 16 keeps that program near 7 GiB of HBM
# next to the 4 GiB index (tests/test_tpu_compile.py pins its fit)
SERVE_BATCH = 16
# cuts for time (the selector trains on fewer queries and epochs than the
# paper's 5,000 x 150)
TRAIN_QUERIES = 512
HOLDOUT_QUERIES = 128
EPOCHS = 20
# phase B parity against the float32 corpus: PQ at nsub 8 over 768 dims
# keeps 8 bytes of each 3,072-byte vector, so the served MRR@10 may trail
# the float reference by more than serve's default 0.02. The tight check
# of the serving path is serve's same-codes comparison, which this leaves
# as it is
PARITY_MRR_TOL = 0.25


class _Tee(io.TextIOBase):
    """Write to the real stdout and keep a copy for the OK-line checks."""

    def __init__(self, out):
        self.out, self.buf = out, io.StringIO()

    def write(self, s):
        self.out.write(s)
        self.buf.write(s)
        return len(s)

    def flush(self):
        self.out.flush()


class _CompileClock:
    """Seconds spent in XLA backend compiles, read per phase."""

    def __init__(self):
        import jax
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs


def _memory():
    import jax
    st = jax.devices()[0].memory_stats() or {}
    return st.get("bytes_in_use"), st.get("peak_bytes_in_use")


def _phase(name, fn, argv, must_print, clock):
    """Run one CLI main(argv); returns None on success, else the reason."""
    print(f"== {name}: {' '.join(argv)}", flush=True)
    c0, t0 = clock.secs, time.perf_counter()
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        rc = fn(argv)
    gc.collect()
    in_use, peak = _memory()
    print(f"== {name}: exit {rc}, {time.perf_counter() - t0:.1f}s wall "
          f"({clock.secs - c0:.1f}s compiling), bytes_in_use={in_use} "
          f"peak_bytes_in_use={peak}", flush=True)
    if rc:
        return f"{name} exited {rc}"
    missing = [m for m in must_print if m not in tee.buf.getvalue()]
    if missing:
        return f"{name} did not print {missing}"
    return None


def run_phases(workdir, *, docs=N_DOCS, clusters=N_CLUSTERS,
               queries=N_QUERIES, batch=SERVE_BATCH,
               train_queries=TRAIN_QUERIES, holdout=HOLDOUT_QUERIES,
               epochs=EPOCHS):
    """Phases A and B through the CLIs. Returns the list of failures."""
    from repro.launch import build_index, serve, train_selector
    clock = _CompileClock()
    size = ["--variant", "full", "--docs", str(docs),
            "--clusters", str(clusters)]
    idx = os.path.join(workdir, "index")
    phases = [
        ("A serve (corpus in HBM)", serve.main,
         size + ["--queries", str(queries), "--batch", str(batch),
                 "--train-queries", str(train_queries),
                 "--epochs", str(epochs)], ["CluSD   MRR@10="]),
        ("B1 build_index (v2 PQ)", build_index.main,
         ["--out", idx] + size + ["--format-version", "2",
                                  "--train-queries", "0"], ["wrote "]),
        ("B2 train_selector", train_selector.main,
         ["--index-dir", idx, "--train-queries", str(train_queries),
          "--holdout-queries", str(holdout), "--epochs", str(epochs),
          "--publish", "--serve-check", "8"], ["serve check OK"]),
        ("B3 serve --index-dir", serve.main,
         ["--index-dir", idx, "--queries", str(queries),
          "--batch", str(batch), "--check-parity",
          "--parity-mrr-tol", str(PARITY_MRR_TOL)], ["parity OK\n"]),
    ]
    failures = []
    for name, fn, argv, must_print in phases:
        why = _phase(name, fn, argv, must_print, clock)
        if why:
            failures.append(why)
            break               # later phases build on earlier ones
    return failures


# the Pallas kernels the phases must run on the chip, by the kernel name
# Mosaic records in each program's `tpu_custom_call`
KERNELS = {"adc_tables": "_tables_kernel", "adc_score_blocks": "_score_kernel",
           "lstm": "_lstm_kernel"}


def kernels_run(dump_dir):
    """For each of KERNELS, the programs this process ran that carry it as
    a Mosaic `tpu_custom_call`. JAX dumps every program's StableHLO (with
    `jax_dump_ir_to` set) before compiling it or loading it from the
    compile cache, so the dump covers exactly the programs the phases
    ran."""
    found = {name: [] for name in KERNELS}
    for fname in sorted(os.listdir(dump_dir)):
        with open(os.path.join(dump_dir, fname)) as f:
            text = f.read()
        if "tpu_custom_call" not in text:
            continue
        program = fname.split("_", 2)[-1].removesuffix("_compile.mlir")
        for name, kernel in KERNELS.items():
            if f'kernel_name = "{kernel}"' in text:
                found[name].append(program)
    return found


def main():
    import jax
    devs = jax.devices()
    dev = devs[0]
    print(f"jax {jax.__version__}, device {dev.platform} "
          f"{dev.device_kind} x {len(devs)}", flush=True)
    if dev.platform != "tpu":
        print("FAIL: no TPU found; this smoke run needs the chip",
              file=sys.stderr)
        return 1
    from repro.common.compile_cache import place_compile_cache
    print(f"compile cache: {place_compile_cache()}")
    print(f"deployment: clusd-msmarco full widths, {N_DOCS} docs "
          f"(cut from 8,841,823), {N_CLUSTERS} clusters (cut from 8,192), "
          f"serving batch {SERVE_BATCH}; selector trained on "
          f"{TRAIN_QUERIES} queries x {EPOCHS} epochs (cut from "
          f"5,000 x 150); PQ parity vs the float corpus within "
          f"{PARITY_MRR_TOL} MRR@10 (serve's default 0.02)", flush=True)
    with tempfile.TemporaryDirectory(prefix="clusd_smoke_") as work:
        dump = os.path.join(work, "programs")
        jax.config.update("jax_dump_ir_to", dump)
        jax.config.update("jax_dump_ir_modes", "stablehlo")
        failures = run_phases(work)
        if os.path.isdir(dump):
            for name, programs in kernels_run(dump).items():
                print(f"{name}: tpu_custom_call in "
                      f"{', '.join(sorted(set(programs))) or 'no program'}")
                if not programs:
                    failures.append(f"no program ran the {name} kernel")
    in_use, peak = _memory()
    print(f"peak_bytes_in_use={peak} bytes_in_use={in_use}")
    if failures:
        print("FAIL: " + "; ".join(failures), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
