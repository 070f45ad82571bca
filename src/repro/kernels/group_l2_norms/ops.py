"""Wrapper for the group-norm reduction kernel."""
from repro.kernels import note_route
from repro.kernels.group_l2_norms.group_l2_norms import group_l2_norms
from repro.kernels.group_l2_norms.ref import group_l2_norms_ref


def group_sq_norms_kernel(w, num_groups: int, *, interpret: bool):
    fallback = bool(w.shape[1] % num_groups)
    note_route("group_l2_norms", w.shape, fallback=fallback,
               interpret=interpret)
    if fallback:
        return group_l2_norms_ref(w, num_groups)
    return group_l2_norms(w, num_groups, interpret=interpret)
