"""Granite 4.0-H (ISSUE 34): the cell's limits against planted departures
from the published equations. These cases were
``tests/test_granite_hybrid.py``'s until PR 45 (a file is one worker's
under ``--dist loadfile``). A CPU run shows results and counts, never a
time."""

import functools

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.models import GraniteHybrid

from helpers.families import config_of, tail_loss_grads, tiny, weights
from helpers.families import _batch
from architectures import granite_hybrid as arch  # noqa: E402  (benchmark/,
#                                           on sys.path by families)
from kinds import train_job  # noqa: E402  (benchmark/, by families)
from lib import modelspec  # noqa: E402

CONFIG = config_of("granite_hybrid")
_tiny = functools.partial(tiny, "granite_hybrid")
_weights = functools.partial(weights, "granite_hybrid")


class _UntiedHead(GraniteHybrid):
    """A head that is not the embedding table (its rows reversed)."""
    def _project_vocab(self, params, x):
        other = {**params, "embed": {
            "tokens": jnp.flip(params["embed"]["tokens"], 0)}}
        return super()._project_vocab(other, x)


FAULTS = {
    None: {},
    "softmax_scale_head_dim_rsqrt_in_place_of_the_multiplier":
        dict(attention_multiplier=None),
    "no_residual_multiplier": dict(residual_multiplier=1.0),
    "logits_not_divided": dict(logits_scaling=1.0),
    "no_embedding_multiplier": dict(embedding_multiplier=1.0),
    "untied_head": {},
    "targets_off_by_one": {},
}


@pytest.mark.parametrize("fault", list(FAULTS), ids=lambda f: f or "none")
def test_the_cells_limits_catch_a_planted_fault(fault):
    """The benchmark's own decision (``kinds/train_job.py`` ``decide`` at
    the configuration's ``check``) on the program's tail logits and loss
    against the reference's: the program passes, each planted departure
    from the published equations does not."""
    right = _tiny(loss_chunk=64)
    cls = _UntiedHead if fault == "untied_head" else GraniteHybrid
    model = cls(size="tiny", loss_chunk=64, **FAULTS[fault])
    params = _weights(right)
    tokens, targets = _batch(right)
    m = modelspec.reference_model(arch, right)
    with jax.default_matmul_precision("highest"):
        want_loss, want_tail = arch.reference(params, tokens, targets, m, 32)
        if fault == "targets_off_by_one":
            targets = jnp.roll(targets, 1, axis=1)
        got_tail, got_loss, _ = tail_loss_grads(model, params, tokens,
                                                targets, grads=False)
    got_loss = float(got_loss)
    numbers = train_job.tail_numbers(got_tail, want_tail, None)
    ok = train_job.decide(numbers, want_loss, got_loss, CONFIG["check"])
    assert ok == (fault is None), numbers
    if fault is None:
        assert numbers["logits_err_max"] < 1e-4 > numbers["loss_err"]
