"""Share of the chip-steps of a fleet on several chips in which a chip
stood done while another still ran: 100 * (1 - sum of `caps.chip_steps` /
(`caps.chips` x the longest of them)) over the window's whole jobs, from
the program's job samples (`slot_active_pct.py::window_totals` says which
they are). On a mesh every chip runs its own machines in its own loop to
their end, with no barrier between chips, and the job ends with the last:
`caps.chip_steps` has, a chip, the most steps any of its machines ran,
which is what that chip's loop ran. A count, exact for a seed; what the
grid's placement over the chips costs. A program whose job samples say
nothing of chips (every one before PR 51), a fleet on one chip and a job
of one machine give nothing to read."""


def read(run, trace):
    import cells

    t = cells._load("metrics", "slot_active_pct", cells.ROOT, "window_totals")(run)
    if t is None or t["caps"].get("chips", 1) < 2:
        return None
    from primesim_tpu.obs import process_store

    # the very samples `window_totals` summed: the jobs' last, its first `jobs`
    samples = [s for s in process_store().samples()
               if s["label"] in ("engine", "fleet") and "caps" in s]
    samples = samples[-len(run["jobs"]):][:t["jobs"]]
    held = sum(s["caps"]["chips"] * max(s["caps"]["chip_steps"]) for s in samples)
    ran = sum(sum(s["caps"]["chip_steps"]) for s in samples)
    return 100.0 * (1.0 - ran / held) if held else None
