"""Machines' worth of HBM the process had passed through, over what lay
there before a job's engine was built, by the time that job's wait ended:
(`alloc_run.peak_bytes_in_use` - `alloc.bytes_in_use`) / `state_bytes` of
the job sample's `place` (the program's `sim/engine.py::job_place`,
`place_run`: three readings of the chip's allocator a job and the bytes of
the state the job was built with, each a list with one value a chip), on
the sample's fullest chip, the largest over the window's jobs. The peak is
the process's high-water mark, so it holds the build, the warm-up, the
parity job and every earlier job of the window: 1.0 and a little (the
loop's temporaries, the trace) says that nothing ever held a second copy
of the machine beside a job's own; 2 says that something did (an engine, a
result or a sample that outlived its job, a loop that does not take its
state in place). On a cell whose machine is over half the chip a second
copy does not read 2: the job raises. A count of the allocator's, not a
time. A program whose samples carry no `place`, no `state_bytes` (every
one before this metric) or no allocator's count (the CPU) gives nothing
to read."""


def read(run, trace):
    try:
        from primesim_tpu.obs import process_store
    except ImportError:
        return None
    n = len(run["jobs"])
    # the window's jobs' samples: the store's last `n` that a fused job committed
    samples = [s for s in process_store().samples()
               if s["label"] in ("engine", "fleet") and "caps" in s][-n:] if n else []
    copies = []
    for s in samples:
        place = s.get("place") or {}
        before = (place.get("alloc") or {}).get("bytes_in_use")
        peak = (place.get("alloc_run") or {}).get("peak_bytes_in_use")
        state = place.get("state_bytes")
        if not (before and peak and state):
            return None
        fullest = max(range(len(peak)), key=peak.__getitem__)
        copies.append((peak[fullest] - before[fullest]) / state[fullest])
    return max(copies) if copies else None
