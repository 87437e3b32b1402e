"""Device milliseconds per step under the scope `s.dir/grp`: the coarse
sharer vector's group work inside phase 3 (the group bits of the accessed
and the victim way, the row gathers from the per-(home tile, group) hop
tables, the masked max and sums that give the invalidation and
back-invalidation latencies, counts and hops), from the traced job. It is
inside `ph_dir_ms_step`; only a machine with `sharer_group` > 1 has it."""


def read(run, trace):
    from phase_ops import phase_ms_step

    return phase_ms_step(run, trace, "/s.dir/grp/")
