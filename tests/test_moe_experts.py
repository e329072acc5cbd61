"""ops/moe_experts.py: a layer's routed experts, the touched ones alone.

The call runs interpreted here (the CPU backend) against the dense
products of models/common.py moe_block on the same seeded int8 leaves;
on the chip tools/chip_kernels.py (`expert_cell_*`) compiles it at the
cells' geometries and times it beside them. One parametrised test: each
case is a line of CASES.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from butterfly_tpu.core.config import tiny
from butterfly_tpu.core.mesh import mesh_ctx
from butterfly_tpu.models.common import (
    experts_in_place, layer_experts, moe_block)
from butterfly_tpu.ops import moe_experts as kernel

L, D, F, LAYER = 3, 128, 256, 1


def config(E, held, first, dtype):
    return tiny("mixtral", hidden_size=D, intermediate_size=F, num_experts=E,
                num_experts_per_tok=8, experts_held=held, experts_first=first,
                dtype=dtype)


def seeded_moe(cfg, key=0):
    """A stack of L layers of experts: int8 codes and a scale an output
    channel, a float router."""
    dt = jnp.dtype(cfg.dtype)
    ks = iter(jax.random.split(jax.random.PRNGKey(key), 8))
    Eh = cfg.local_experts

    def codes(*shape):
        q8 = jax.random.randint(next(ks), (L, Eh) + shape, -127, 128, jnp.int8)
        s = jax.random.uniform(next(ks), (L, Eh, 1, shape[1]), minval=.5,
                               maxval=1.5) / (74 * shape[0] ** .5)
        return {"q8": q8, "s": s.astype(dt)}

    return {"router": jax.random.normal(next(ks), (L, D, cfg.num_experts), dt),
            "w_gate": codes(D, F), "w_up": codes(D, F), "w_down": codes(F, D)}


def both_paths(cfg, moe, x, ok):
    """(the Mosaic call's result, the dense products') of layer LAYER of
    the stack, through moe_block as the packed step's loops hand it a
    layer: the experts whole and the index beside them, or a slice."""
    lp = jax.tree.map(lambda a: a[LAYER], moe)
    held = {n: moe[n] for n in kernel.LEAVES}
    rest = {k: v for k, v in lp.items() if k not in held}
    placed = layer_experts({"moe": rest}, held, jnp.int32(LAYER))["moe"]
    return (np.asarray(moe_block(x, placed, cfg, ok=ok), np.float32),
            np.asarray(moe_block(x, lp, cfg, ok=ok), np.float32))


def untouched(cfg, moe, x, ok):
    """The held experts no real row of the step chose at layer LAYER."""
    from butterfly_tpu.models.common import expert_gates
    comb = expert_gates(x, jax.tree.map(lambda a: a[LAYER], moe), cfg)
    hit = np.asarray(jnp.any((comb != 0) & ok[..., None], axis=(0, 1)))
    return np.flatnonzero(~hit)


def parity(rows, E, held, first, dtype, tol):
    cfg = config(E, held, first, dtype)
    moe = seeded_moe(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (rows, 1, D),
                          jnp.dtype(dtype))
    ok = (jnp.arange(rows) % 5 != 3)[:, None]           # a fifth is filler
    got, want = both_paths(cfg, moe, x, ok)
    real = np.asarray(ok[:, 0])
    assert np.abs(want[real]).max() > 0.05
    assert np.abs(got[real] - want[real]).max() \
        <= tol * np.abs(want[real]).max()
    # a row that is not real chose nothing: exact zeros, not its products
    assert not got[~real].any()


def nan_scales(rows):
    """The untouched experts' SCALES are NaN: the dense products read
    them (NaN times a gate of zero), the call does not."""
    cfg = config(64, 16, 32, "float32")
    moe = seeded_moe(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (rows, 1, D))
    ok = (jnp.arange(rows) < 6)[:, None]        # few real rows: some idle
    idle = untouched(cfg, moe, x, ok)
    assert 0 < len(idle) < 16
    clean, _ = both_paths(cfg, moe, x, ok)
    dirty = jax.tree.map(lambda a: a, moe)
    for name in kernel.LEAVES:
        dirty[name]["s"] = moe[name]["s"].at[LAYER, idle].set(jnp.nan)
    got, want = both_paths(cfg, dirty, x, ok)
    assert np.isnan(want).any()
    assert np.isfinite(got).all() and np.array_equal(got, clean)


def none_touched(rows):
    """No real row chose a held expert (the router's columns of the held
    experts are pushed out of every row's k): zeros, whatever the codes."""
    cfg = config(64, 16, 0, "float32")
    moe = seeded_moe(cfg)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (rows, 1, D)))
    moe["router"] = moe["router"].at[:, :, :16].set(-1.0)   # x > 0: last
    ok = jnp.ones((rows, 1), bool)
    assert len(untouched(cfg, moe, x, ok)) == 16
    got, want = both_paths(cfg, moe, x, ok)
    assert not got.any() and not want.any()


#: the choice of path by what a step shows: the cells' own geometries
#: (rows of the mixed and of the decode block, k, E, held, D, F) and what
#: turns the call off whatever the geometry
GEOMETRIES = {
    "glm5-ep16.think mixed": (64, 8, 256, 16, 6144, 2048, True),
    "glm5-ep16.think decode": (32, 8, 256, 16, 6144, 2048, True),
    "keye30b.think mixed": (64, 8, 128, 128, 2048, 768, True),
    "keye30b.think decode": (32, 8, 128, 128, 2048, 768, True),
    "smallthinker21b.batch mixed": (64, 6, 64, 64, 2560, 768, False),
    "smallthinker21b.batch decode": (32, 6, 64, 64, 2560, 768, True),
    "joyai48b.longthink mixed": (128, 8, 256, 256, 2048, 768, False),
    "joyai48b.longthink decode": (96, 8, 256, 256, 2048, 768, False),
    "granite4h.rollout decode": (128, 10, 72, 72, 4096, 768, False),
    "xing29b.rollout decode": (128, 8, 64, 64, 2048, 1024, False),
}


def choice(name, leaves="int8", use_kernel=True, mesh=None):
    rows, k, E, held, d, f, want = GEOMETRIES[name]
    cfg = tiny("mixtral", hidden_size=d, intermediate_size=f, num_experts=E,
               num_experts_per_tok=k,
               experts_held=held if held != E else 0)

    def leaf(*shape):
        if leaves == "float":
            return jax.ShapeDtypeStruct((2, held) + shape, jnp.bfloat16)
        return {"q8": jax.ShapeDtypeStruct((2, held) + shape, jnp.int8),
                "s": jax.ShapeDtypeStruct((2, held, 1, shape[1]),
                                          jnp.bfloat16)}

    stack = {"ln2": {"scale": jax.ShapeDtypeStruct((2, d), jnp.bfloat16)},
             "moe": {"router": jax.ShapeDtypeStruct((2, d, E), jnp.bfloat16),
                     "w_gate": leaf(d, f), "w_up": leaf(d, f),
                     "w_down": leaf(f, d)}}
    if mesh:
        from butterfly_tpu.core.config import MeshConfig
        from butterfly_tpu.core.mesh import make_mesh
        mesh = make_mesh(MeshConfig(tensor=mesh), jax.devices()[:mesh])
    with mesh_ctx(mesh):
        rest, held_back = experts_in_place(stack, rows, cfg, use_kernel)
    taken = held_back is not None
    assert taken == (want and leaves == "int8" and use_kernel and not mesh)
    # a stack that does not take the call is the SAME tree: its loop and
    # its program stay what they were
    assert taken or rest is stack
    assert not taken or set(held_back) == set(kernel.LEAVES)


CASES = {
    **{f"parity {rows} rows, 16 of 64 held, float32":
       (parity, rows, 64, 16, 16, "float32", 2e-5) for rows in (32, 64)},
    **{f"parity {rows} rows, all of 64, float32":
       (parity, rows, 64, 0, 0, "float32", 2e-5) for rows in (32, 64)},
    "parity 64 rows, 16 of 64 held, bfloat16":
        (parity, 64, 64, 16, 16, "bfloat16", 3e-2),
    "parity 24 rows (padded to whole tiles), all of 64, bfloat16":
        (parity, 24, 64, 0, 0, "bfloat16", 3e-2),
    "untouched experts' scales are NaN, 32 rows": (nan_scales, 32),
    "untouched experts' scales are NaN, 64 rows": (nan_scales, 64),
    "no held expert touched, 32 rows": (none_touched, 32),
    **{f"choice: {name}": (choice, name) for name in GEOMETRIES},
    "choice: float leaves keep the dense products":
        (choice, "glm5-ep16.think mixed", "float"),
    "choice: kernels off (the CPU's engines, the reference)":
        (choice, "glm5-ep16.think mixed", "int8", False),
    "choice: a mesh axis larger than 1":
        (choice, "glm5-ep16.think mixed", "int8", True, 2),
}


@pytest.mark.parametrize("case", CASES.values(), ids=CASES.keys())
def test_the_routed_experts_call(case):
    """ops/moe_experts.py interpreted against moe_block's dense products
    on seeded int8 leaves (a layer in the middle of a stack, some rows
    not real, a held share of the experts and all of them), what it
    leaves unread, and who takes it."""
    fn, *args = case
    fn(*args)
