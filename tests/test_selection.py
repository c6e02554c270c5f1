"""``ops/selection.compact``: the dense-prefix branch returns the buffers
as they are, the other branch gathers, and below ``new_nrows`` nobody
can tell which one ran."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.columnar import dtypes as dts
from spark_rapids_tpu.columnar.column import Column
from spark_rapids_tpu.ops import selection
from spark_rapids_tpu.ops.expressions import ColVal

CAP = 64
NROWS = 50  # the rows a mask may keep; the rest is a batch's padding


def _mask(kind):
    rows = np.arange(CAP)
    return {"all": rows < CAP,
            "none": np.zeros(CAP, dtype=bool),
            "prefix": rows < 23,
            "scattered": (rows % 3 != 1) & (rows < NROWS),
            "all_short": rows < NROWS}[kind]


def _column(kind, rng):
    """(ColVal, the rows as Python values, None for a NULL)."""
    valid = rng.random(CAP) > 0.3
    if kind == "int64":
        vals = rng.integers(-2**40, 2**40, CAP)
        return (ColVal(dts.INT64, jnp.asarray(vals), jnp.asarray(valid)),
                [int(v) if ok else None for v, ok in zip(vals, valid)])
    if kind == "float64":
        vals = rng.standard_normal(CAP)
        return ColVal(dts.FLOAT64, jnp.asarray(vals)), list(vals)
    if kind == "string":
        texts = ["r%d" % i * (i % 4) for i in range(CAP)]
        lens = np.array([len(t) for t in texts], dtype=np.int32)
        col = Column.from_string_buffers(
            np.concatenate([[0], np.cumsum(lens)]).astype(np.int32),
            np.frombuffer("".join(texts).encode(), dtype=np.uint8), CAP,
            validity=valid, capacity=CAP)
        rows = [t if ok else None for t, ok in zip(texts, valid)]
    else:
        # the all-NULL string column the file scan puts where it pruned
        # one (io/readers.py _finish_batch): no chars, flat offsets
        col = Column.from_string_buffers(
            np.zeros(NROWS + 1, dtype=np.int32), np.zeros(0, np.uint8),
            NROWS, validity=np.zeros(NROWS, dtype=bool), capacity=CAP)
        rows = [None] * CAP
    return (ColVal(dts.STRING, jnp.asarray(col.data),
                   jnp.asarray(col.validity), jnp.asarray(col.offsets)),
            rows)


def _rows(col, n):
    """The first ``n`` rows of a ColVal as Python values."""
    valid = np.ones(n, dtype=bool) if col.validity is None \
        else np.asarray(col.validity)[:n]
    values = np.asarray(col.values)
    if col.offsets is None:
        return [values[i].item() if valid[i] else None for i in range(n)]
    offs = np.asarray(col.offsets)
    return [bytes(values[offs[i]:offs[i + 1]]).decode() if valid[i]
            else None for i in range(n)]


@pytest.mark.parametrize("column", ["int64", "float64", "string",
                                    "placeholder"])
@pytest.mark.parametrize("mask", ["all", "none", "prefix", "scattered",
                                  "all_short"])
def test_compact_equals_the_unconditional_gather(mask, column, rng):
    col, rows = _column(column, rng)
    keep = _mask(mask)
    (out,), n = jax.jit(selection.compact)([col], jnp.asarray(keep))
    n = int(n)
    assert n == keep.sum()
    assert _rows(out, n) == [r for r, k in zip(rows, keep) if k]
    # the compaction with no branch, op by op as the joins run it
    (ref,), ref_n = selection.compact_by_gather([col], jnp.asarray(keep))
    assert int(ref_n) == n
    if col.validity is None:
        assert out.validity is None
    else:
        np.testing.assert_array_equal(np.asarray(out.validity)[:n],
                                      np.asarray(ref.validity)[:n])
    if col.offsets is None:
        np.testing.assert_array_equal(np.asarray(out.values)[:n],
                                      np.asarray(ref.values)[:n])
        return
    # gather's padding, whichever branch ran: offsets flat after the
    # last kept row, chars zero from their total on
    offs, chars = np.asarray(out.offsets), np.asarray(out.values)
    np.testing.assert_array_equal(offs, np.asarray(ref.offsets))
    np.testing.assert_array_equal(chars, np.asarray(ref.values))
    assert (offs[n:] == offs[n]).all()
    assert not chars[offs[n]:].any()


def _primitives(jaxpr, into_cond=False):
    """Names of the primitives of ``jaxpr`` and of what it calls, the
    branches of a ``cond`` left out unless asked for."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        if eqn.primitive.name == "cond" and not into_cond:
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names.extend(_primitives(sub, into_cond))
    return names


def _conds(jaxpr):
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_conds(sub))
    return found


def test_filter_stage_gathers_only_inside_one_conditional():
    """A lowered ``jit_filter_stage_*``: the predicate and the mask's two
    reduces outside, one ``cond``, and the permutation's scan, scatter
    and gathers in one of its branches only."""
    from spark_rapids_tpu.ops import predicates as P
    from spark_rapids_tpu.ops.compiler import FilterStageFn
    from spark_rapids_tpu.ops.expressions import BoundReference, Literal
    row = [dts.INT64, dts.FLOAT64, dts.DATE32, dts.STRING]
    flat = [(jnp.zeros(CAP, dt.storage), jnp.ones(CAP, jnp.bool_), None)
            for dt in row[:3]]
    flat.append((jnp.zeros(256, jnp.uint8), None,
                 jnp.zeros(CAP + 1, jnp.int32)))
    refs = [BoundReference(i, dt) for i, dt in enumerate(row)]
    stage = FilterStageFn(
        P.GreaterThan(refs[2], Literal("1995-03-15", dts.DATE32)),
        refs, row)
    jaxpr = jax.make_jaxpr(stage._run)(flat, jnp.int32(NROWS)).jaxpr
    moving = {"gather", "scatter", "cumsum", "sort"}
    assert not moving & set(_primitives(jaxpr))
    (cond,) = _conds(jaxpr)
    branches = [set(_primitives(b.jaxpr, into_cond=True))
                for b in cond.params["branches"]]
    assert sum(bool(moving & b) for b in branches) == 1
    moved = next(b for b in branches if moving & b)
    assert {"gather", "scatter", "cumsum"} <= moved
    text = jax.jit(stage._run).lower(flat, jnp.int32(NROWS)).as_text()
    assert text.count("stablehlo.case") == 1
