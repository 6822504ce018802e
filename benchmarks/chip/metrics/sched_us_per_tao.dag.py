"""Host time the runtime spent admitting, placing and committing, per TAO
committed, over the window's runs, in us: the self time of its
``repro.runtime.admit``, ``place`` and ``commit`` sites
(``WorkloadResult.host_ns``) over ``WorkloadResult.counts["commits"]``,
both kept only while the profiler records.  Layer: scheduler and vehicle.
Moves ``taos_per_s``."""


def read(run):
    ns = commits = 0
    for _, res, _ in getattr(run.cell, "runs", ()):
        host = getattr(res, "host_ns", None)
        if host:
            ns += host["admit"] + host["place"] + host["commit"]
            commits += res.counts["commits"]
    return ns / commits / 1e3 if commits else None
