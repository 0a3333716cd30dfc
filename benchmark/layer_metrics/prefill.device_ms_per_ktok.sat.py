"""model step, serving: device milliseconds of the capture's WHOLE prefill runs per 1,000 bucket tokens, each jit_fwd run filed by its fingerprint under the program that the .fetch annotations paired with the runs in order name (tag program; tag run, the flight's ordinal in stats()["runs"], says whether the capture lost a fetch between two of a program, and only the pairs before such a gap vote); by bucket, with the runs kept and dropped, the ordinals against the window's and the host-to-device clock offset, in the info line; nothing where the capture's .fetch annotations carry no tags."""
from benchmark.harness import phases, run_ledger


@phases.quiet
def read(ctx):
    return run_ledger.prefill_device_ms_per_ktok(ctx)
