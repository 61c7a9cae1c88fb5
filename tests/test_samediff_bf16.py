"""SameDiff mixed-precision policy (r5 verdict item 3).

The nn engines' ``dtype="BFLOAT16"`` policy (fp32 masters, bf16 compute)
now applies to the SameDiff/import path via ``sd.set_dtype`` — mirroring
SameDiff TrainingConfig's dtype† (SURVEY.md §7.3.8; reference mount empty,
citation upstream-relative, unverified). Validated against the f32 oracle
within tolerance bands, the same discipline the engines' bf16 tests use.
"""

import contextlib
import itertools

import numpy as np
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.autodiff.samediff import VARIABLE, SameDiff
from deeplearning4j_tpu.nn.updaters import Adam, Nesterovs, Sgd
from deeplearning4j_tpu.runtime import sentinel, telemetry


def _mlp(seed=0):
    rng = np.random.default_rng(seed)
    sd = SameDiff.create()
    x = sd.placeholder("x")
    y = sd.placeholder("y")
    w1 = sd.var("w1", rng.normal(0, 0.4, (8, 16)).astype(np.float32))
    b1 = sd.var("b1", np.zeros(16, np.float32))
    w2 = sd.var("w2", rng.normal(0, 0.4, (16, 3)).astype(np.float32))
    b2 = sd.var("b2", np.zeros(3, np.float32))
    h = sd.call("act.tanh", x.mmul(w1) + b1)
    logits = h.mmul(w2) + b2
    sd.set_loss(sd.call("loss.softmax_ce_logits", y, logits))
    return sd


def _feeds(seed=1, n=6):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        x = rng.normal(size=(32, 8)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 32)]
        out.append({"x": x, "y": y})
    return out


def test_bf16_policy_tracks_f32_oracle():
    feeds = _feeds()
    f32 = _mlp().set_updater(Sgd(learning_rate=0.2))
    h32 = f32.fit(feeds, epochs=4)
    b16 = _mlp().set_updater(Sgd(learning_rate=0.2)).set_dtype("BFLOAT16")
    h16 = b16.fit(feeds, epochs=4)
    # both train; curves agree within bf16 tolerance bands
    assert h32.losses[-1] < h32.losses[0]
    assert h16.losses[-1] < h16.losses[0]
    np.testing.assert_allclose(h16.losses[-1], h32.losses[-1],
                               rtol=0.05, atol=0.02)
    # masters stayed fp32 under the policy
    for n in ("w1", "w2", "b1", "b2"):
        assert b16._values[n].dtype == jnp.float32, n


def test_bf16_policy_retraces_and_serves_inference_in_recorded_dtype():
    feeds = _feeds(n=2)
    sd = _mlp().set_updater(Adam(learning_rate=1e-2))
    sd.fit(feeds, epochs=1)
    spec_f32 = sd._fn_cache["__fit_step__"][0]
    sd.set_dtype("BFLOAT16")
    assert "__fit_step__" not in sd._fn_cache  # policy change invalidates
    sd.fit(feeds, epochs=1)
    assert sd._fn_cache["__fit_step__"][0] != spec_f32
    # exec/output stays in the recorded dtype (imported-graph parity)
    out = sd.output({"x": feeds[0]["x"], "y": feeds[0]["y"]}, [sd.loss_name])
    assert np.asarray(out[sd.loss_name]).dtype == np.float32


# ---------------------------------------------------------------------------
# ISSUE 34: fit() prepares a call in ONE compiled launch (carry copies, the
# constants' casts, the optimizer state) and, with no listener attached, reads
# a step's loss behind the next launch. Neither may change a bit.
# ---------------------------------------------------------------------------
def _frozen_mlp(variables=4, seed=0):
    """``variables`` trainable leaves (pairs of weight and bias) behind a
    frozen floating constant, so the call's preparation has a carry, a
    constant to cast and an optimizer state."""
    rng = np.random.default_rng(seed)
    sd = SameDiff.create()
    x, y = sd.placeholder("x"), sd.placeholder("y")
    h = x.mmul(sd.constant(
        "w_frozen", rng.normal(0, 0.4, (8, 16)).astype(np.float32)))
    for k in range(variables // 2 - 1):
        w = sd.var(f"w{k}", rng.normal(0, 0.3, (16, 16)).astype(np.float32))
        b = sd.var(f"b{k}", np.zeros(16, np.float32))
        h = sd.call("act.tanh", h.mmul(w) + b)
    w = sd.var("w_out", rng.normal(0, 0.4, (16, 3)).astype(np.float32))
    b = sd.var("b_out", np.zeros(3, np.float32))
    sd.set_loss(sd.call("loss.softmax_ce_logits", y, h.mmul(w) + b))
    return sd


def _eager_fit(sd, feeds_list, calls):
    """``calls`` fit() calls as the parent of ISSUE 34 made them: the carry,
    the constants' casts and the optimizer state prepared eagerly
    (``cast_floating`` + ``init_state``), then the compiled step a feed, the
    loss read after every step. -> (losses, values, iteration)"""
    step = sd._fit_step_cached()
    names = [n for n, v in sd._vars.items() if v.kind == VARIABLE]
    cdt = dtypes.resolve(sd.dtype)
    values, counters = dict(sd._values), sentinel.init_counters()
    losses, i = [], sd.iteration
    for _ in range(calls):
        tv = {n: values[n] for n in names}
        other = {n: v for n, v in values.items() if n not in names}
        carry = tv
        if sd.fused_updater_active():
            carry = (tv, dtypes.cast_floating(tv, cdt))
        if dtypes.is_mixed(sd.dtype):
            other = dtypes.cast_floating(other, cdt)
        opt = sd.updater.init_state(tv)
        for feeds in feeds_list:
            feeds = {k: jnp.asarray(v) for k, v in feeds.items()}
            carry, opt, counters, loss = step(
                carry, opt, other, jnp.asarray(i, jnp.int32), feeds, counters)
            losses.append(float(loss))
            i += 1
        values.update(sd._carry_masters(carry))
    return losses, values, i


class _Scores:
    def __init__(self):
        self.seen = []

    def iteration_done(self, model, iteration, epoch):
        self.seen.append((iteration, model.score()))

    def on_epoch_end(self, model):
        pass


@pytest.mark.parametrize("dtype,updater,l2,listener", list(itertools.product(
    ("FLOAT", "BFLOAT16"), ("adam", "nesterovs"), (0.0, 1e-2),
    (False, True))))
def test_fit_is_bit_equal_to_an_eagerly_prepared_loop(dtype, updater, l2,
                                                      listener):
    def make():
        sd = _frozen_mlp(variables=6, seed=4)
        sd.set_training_config(
            updater=Adam(learning_rate=1e-2) if updater == "adam"
            else Nesterovs(learning_rate=0.05, momentum=0.9), l2=l2)
        return sd.set_dtype(dtype)

    feeds = _feeds(seed=2, n=3)
    want_losses, want_values, want_i = _eager_fit(make(), feeds, calls=2)
    sd, scores = make(), _Scores()
    got = []
    for _ in range(2):
        got += sd.fit(feeds, listeners=[scores] if listener else None).losses
    assert got == want_losses
    assert sd.iteration == want_i == 6
    assert sd.score() == want_losses[-1]
    assert sd._values.keys() == want_values.keys()
    for n, v in want_values.items():
        assert sd._values[n].dtype == v.dtype, n
        np.testing.assert_array_equal(np.asarray(sd._values[n]),
                                      np.asarray(v), err_msg=n)
    if listener:
        assert scores.seen == list(zip(range(1, 7), want_losses))


@contextlib.contextmanager
def _eager_primitives():
    """The names of the primitives JAX dispatches eagerly inside the block.
    ``dispatch.apply_primitive`` (the frame a device trace's idle gaps name)
    is bound into every primitive's impl rule at import, so the count wraps
    the first thing it calls, once a dispatch."""
    from jax._src import dispatch
    real, names = dispatch.xla_primitive_callable, []

    def counting(prim, **params):
        names.append(prim.name)
        return real(prim, **params)

    dispatch.xla_primitive_callable = counting
    try:
        yield names
    finally:
        dispatch.xla_primitive_callable = real


def _compiles(site):
    return [e["cause"] for e in telemetry.compile_events(site)]


def test_a_warm_fit_dispatches_no_eager_primitive_per_variable():
    with _eager_primitives() as names:
        jnp.zeros_like(jnp.ones(3)) + 1
    assert len(names) >= 2  # the count sees eager dispatches

    prepare = telemetry.registry.get("samediff.fit.prepare")
    readback = telemetry.registry.get("samediff.fit.readback")
    compiles = telemetry.registry.get("compile.events")
    feeds, counts = _feeds(seed=3, n=4), {}
    for variables in (4, 40):
        sd = _frozen_mlp(variables).set_updater(Adam(learning_rate=1e-3))
        sd.set_dtype("BFLOAT16")
        assert len(sd.variables()) == variables
        sd.fit(feeds)
        before = (prepare.value(decision="compiled"),
                  readback.value(decision="deferred"), compiles.total())
        with _eager_primitives() as names:
            sd.fit(feeds)
        counts[variables] = len(names)
        assert (prepare.value(decision="compiled"),
                readback.value(decision="deferred"), compiles.total()) == \
            (before[0] + 1, before[1] + 1, before[2])
    # before ISSUE 34: an astype a VARIABLE, one a floating constant and a
    # zeros_like a state leaf, 25 dispatches at 4 variables and 205 at 40
    assert counts[40] == counts[4] <= 2, counts


def test_fit_rebuilds_both_programs_once_when_the_spec_changes():
    telemetry.reset_compile_events()
    feeds = _feeds(seed=3, n=2)
    sd = _frozen_mlp().set_updater(Adam(learning_rate=1e-3))
    sd.fit(feeds)
    sd.fit(feeds)
    assert _compiles("samediff.fit_step") == ["first_build"]
    assert _compiles("samediff.fit_prepare") == ["first_build"]
    sd.set_dtype("BFLOAT16")
    assert not any(k in sd._fn_cache for k in sd._FIT_PROGRAMS)
    sd.fit(feeds)
    sd.fit(feeds)
    sd.set_updater(Nesterovs(learning_rate=0.05))
    sd.fit(feeds)
    sd.fit(feeds)
    for site in ("samediff.fit_step", "samediff.fit_prepare"):
        assert _compiles(site) == ["first_build", "dtype_policy",
                                   "config_change"], site
    # Nesterovs' state is one tree where Adam's was two: the preparation
    # was traced for the updater it was built with
    readback = telemetry.registry.get("samediff.fit.readback")
    per_step = readback.value(decision="per_step")
    sd.fit(feeds, listeners=[_Scores()])
    assert readback.value(decision="per_step") == per_step + 1
