"""Share of the element-steps the device ran for elements already at END:
100 * (1 - sum of `caps.element_steps` / (`caps.elements` x `steps`)) over
the window's whole jobs, from the program's job samples
(`slot_active_pct.py::window_totals` says which they are). One dispatch
runs all its machines until the longest has finished, the others frozen
by the vmapped loop's select-masked carry: a count, exact for a seed. A
program whose fleet commits no sample gives nothing to read."""


def read(run, trace):
    import cells

    t = cells._load("metrics", "slot_active_pct", cells.ROOT, "window_totals")(run)
    if t is None or not t["caps"].get("elements"):
        return None
    from primesim_tpu.obs import process_store

    # the very samples `window_totals` summed: the jobs' last, its first `jobs`
    samples = [s for s in process_store().samples()
               if s["label"] in ("engine", "fleet") and "caps" in s]
    samples = samples[-len(run["jobs"]):][:t["jobs"]]
    ran = sum(s["caps"]["elements"] * s["steps"] for s in samples)
    live = sum(sum(s["caps"]["element_steps"]) for s in samples)
    return 100.0 * (1.0 - live / ran) if ran else None
