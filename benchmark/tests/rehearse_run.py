"""``benchmark/run.py`` with the CPU behind pretended chips, for rehearsals
alone.  run.py itself has only the chip's path; everything that lets it run
without one is patched in HERE, from outside:

    python rehearse_run.py <root of a copy> <pretended chips> <run.py's arguments>

The cluster is told it has ``chips`` TPU chips (the workers that lease them
find the CPU backend, and the run expects that), an unknown device kind
reads the v5e's row of peaks, and where a trace has no device plane the XLA
CPU client's threads stand in for one, so that the path from trace to
result line is exercised.  The line says platform "cpu": no measurement.
"""
import sys


def patch(root: str, chips: int, platform: str = "cpu"):
    """Returns the copy's ``benchmark.run`` module, patched."""
    sys.path.insert(0, root)
    import benchmark.run as run
    from benchmark.harness import cluster, peaks, trace

    cluster.PLATFORM = platform
    if chips:
        cluster.chips_on_host = lambda: chips

    def start():
        import ray_tpu

        return ray_tpu.init(
            mode="cluster", num_cpus=4, num_tpus=chips,
            config={"object_store_backend": "pool",
                    "session_dir_root": cluster.session_root()})

    cluster.start = start
    peaks_for = peaks.peaks_for
    peaks.peaks_for = lambda kind: peaks_for("TPU v5 lite")
    load = trace.load

    def load_with_stand_in(path, host_names=()):
        from jax.profiler import ProfileData

        t = load(path, host_names)
        if not t.devices:
            stand_in = trace.DevicePlane("/host:CPU as device")
            for plane in ProfileData.from_file(path).planes:
                for line in plane.lines:
                    if plane.name.startswith("/host:") \
                            and line.name.startswith("tf_XLA"):
                        stand_in.ops.extend(
                            (e.name, e.start_ns, e.duration_ns)
                            for e in line.events if e.duration_ns > 0
                            and not e.name.startswith(("Thread", "end:")))
            if stand_in.ops:
                t.devices.append(stand_in)
        return t

    trace.load = load_with_stand_in
    return run


if __name__ == "__main__":
    sys.exit(patch(sys.argv[1], int(sys.argv[2])).main(sys.argv[3:]))
