"""The operations and bytes ONE decode update of a state-space mixer's
recurrent state needs (one layer, every live slot: ray_tpu/ops/ssm.py's
update of one column a slot), whatever implements it: ONE move of the
state, in once and out once. Beside benchmark/costs.py, which holds the
other kernels'; kept with the benchmark so that no PR that claims a gain
can change them."""

from __future__ import annotations


def ssm_update_bytes(active: int, heads: int, head_p: int, state: int,
                     groups: int, state_itemsize: int = 4,
                     itemsize: int = 2) -> float:
    """The least the update must move: every live slot's state [heads,
    state, head_p] read ONCE and written ONCE at the state's precision,
    and the column's operands: x [heads, head_p], B and C [groups, state]
    at the activations' precision, dt [heads] and the read-out y [heads,
    head_p] float32. Idle lanes of a bucket width, a gathered copy and a
    scattered one are NOT counted, so each shows as lost share."""
    moved = 2 * heads * state * head_p * state_itemsize
    column = (heads * head_p + 2 * groups * state) * itemsize \
        + heads * 4 + heads * head_p * 4
    return float(active * (moved + column))


def ssm_update_flops(active: int, heads: int, head_p: int,
                     state: int) -> float:
    """A state element: the decay's product, the outer product's multiply
    and add, the read-out's multiply and add."""
    return 5.0 * active * heads * state * head_p
