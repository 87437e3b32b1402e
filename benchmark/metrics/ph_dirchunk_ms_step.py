"""Device milliseconds per step under the scope `s.dir/chunk`: the full
sharer map's invalidation-target reductions inside phase 3, walked in
blocks of `sharer_chunk_words` words (a scan whose body unpacks a block of
the accessed and the victim way's sharer bits and takes the masked max and
sums that give the invalidation and back-invalidation latencies, counts
and hops), from the traced job. It is inside `ph_dir_ms_step`; only a
machine with `sharer_chunk_words` > 0 has it."""


def read(run, trace):
    from phase_ops import phase_ms_step

    return phase_ms_step(run, trace, "/s.dir/chunk/")
