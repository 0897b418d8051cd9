"""Device time per residue of work, for the ``*_device_ns_per_residue``
metrics: the summed device time of the programs matching the metric
file's ``patterns`` in the traced job, over all chips, divided by that
job's ``counter`` (the summed length of the reads the pass handles).
None when either is missing."""


def read(ctx, spec):
    tr = ctx.get("trace")
    job = ctx.get("traced_job")
    if tr is None or job is None:
        return None
    residues = job["snap"]["counters"].get(spec["counter"])
    device_s = tr.program_s(spec["patterns"])
    if not residues or device_s is None:
        return None
    return device_s * 1e9 / residues
