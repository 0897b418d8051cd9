"""The realigner's own host wall: its phase spans (the file's ``spans``,
``realign.*``) summed over the window's jobs other than the traced one,
per million input reads.  None when no job recorded any of them."""


def read(ctx, spec):
    jobs = ctx["layer_jobs"]
    walls = [j["snap"]["spans"].get(name) for j in jobs
             for name in spec["spans"]]
    if not any(walls):
        return None
    reads = sum(j["reads"] for j in jobs)
    return sum(w["total_s"] for w in walls if w) / (reads / 1e6)
