"""``benchmark/run.py`` with the traced serving run's capture broken, one
way at a time, from outside (run.py has no flag and reads no variable for
it):

    python3 benchmark/tests/capture_faults.py <fault> <run.py's arguments>

    slow[=S]   the capture's budget is S seconds (default 2: less than
               TRACE_SECONDS, so any capture outlives it)
    raises     the replica's ``bench_trace`` raises
    empty      ``bench_trace`` returns a directory with no ``.xplane.pb``

Each has to end with exit code 1 and ``benchmark: FAILED: BenchFailure(...)``
as the last line of stderr, naming which of the three it was.  On the chip:
``chiprun -- python3 benchmark/tests/capture_faults.py slow=4 --workload
serve-olmoe-1b-7b-sat --seed 7 --trace 1``; on the CPU ``--rehearse <root>``
before run.py's arguments runs a tiny cell of rehearsal.py's copy
(test_capture.py).
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
FAULTS = ("slow", "raises", "empty")


def patch(run, fault: str) -> None:
    """``run``: the ``benchmark.run`` module about to be driven."""
    from benchmark.harness import serve_runner, traced_replica

    name, _, value = fault.partition("=")
    if name == "slow":
        during = serve_runner._during
        serve_runner._during = lambda *a, **kw: during(
            *a, budget_s=float(value or 2.0), **kw)
        return
    real = traced_replica.TracedLLMDeployment

    # defined here, in ``__main__``: pickled to the replica by value
    class Raises(real):
        def bench_trace(self, log_dir: str, seconds: float) -> str:
            raise RuntimeError("capture_faults: start_trace refused")

    class Empty(real):
        def bench_trace(self, log_dir: str, seconds: float) -> str:
            os.makedirs(os.path.join(log_dir, "plugins", "profile", "t"))
            with open(os.path.join(log_dir, "plugins", "profile", "t",
                                   "host.trace.json.gz"), "wb"):
                pass
            return log_dir

    traced_replica.TracedLLMDeployment = {"raises": Raises,
                                          "empty": Empty}[name]


def main(argv) -> int:
    fault, argv = argv[0], argv[1:]
    if fault.partition("=")[0] not in FAULTS:
        print(__doc__, file=sys.stderr)
        return 2
    if argv[:1] == ["--rehearse"]:
        import rehearse_run

        run = rehearse_run.patch(argv[1], 1)
        argv = argv[2:]
    else:
        sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
        import benchmark.run as run
    patch(run, fault)
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
