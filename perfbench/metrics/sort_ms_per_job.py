"""Device time of HLO ``sort`` ops per traced job, per chip: the
reduce-side key sort and the map-side bucket sort together. Only the
``sort`` op: the wide sort's payload gather by the sorted index is an
anonymous fusion, classed ``other``, until the program names it."""


def read(run):
    t = run.trace
    if t is None or not t.class_s.get("sort"):
        return None
    return 1e3 * t.class_s["sort"] / t.jobs
