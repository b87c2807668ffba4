"""``correct`` has to come out false when the timed path is broken
underneath: a train step that returns its state unchanged or trains on a
part of the batch, a served token altered where it is produced.  The harness's look for a chip is skipped;
the rest of a run is driven as it is on the chip."""

import pytest

from benchmark import manifest, serving
from benchmark.kinds import train_step
from benchmark.tests import rehearsal

BY_KIND = {}
for _cell in manifest.load()["workloads"]:
    BY_KIND.setdefault(manifest.traffic_of(_cell)["kind"], _cell["name"])


def failed_checks(result_checks):
    return [c["name"] for c in result_checks if not c["ok"]]


@pytest.fixture
def checks(monkeypatch):
    """Collect the checks a run printed."""
    seen = []
    real = rehearsal.manifest.kind_of

    def spy(traffic):
        kind = real(traffic)

        class Spy:
            @staticmethod
            def run(ctx):
                out = kind.run(ctx)
                seen.extend(out["checks"])
                return out
        return Spy
    monkeypatch.setattr(rehearsal.manifest, "kind_of", spy)
    return seen


def test_step_that_returns_its_state_unchanged(monkeypatch, tmp_path,
                                               checks):
    def frozen(self):
        x, y = self.feed[self.steps % len(self.feed)]
        _, _, loss = self.fn(self.params, self.opt_state, x, y)
        self.steps += 1
        return loss

    monkeypatch.setattr(train_step.Step, "__call__", frozen)
    result = rehearsal.rehearse(monkeypatch, tmp_path,
                                BY_KIND["train_step"])
    assert result["correct"] is False
    bad = failed_checks(checks)
    assert "delta_norm_gap" in bad and "grad_norm_gap" in bad


def test_step_that_trains_on_a_part_of_the_batch(monkeypatch, tmp_path,
                                                 checks):
    def partial(self):
        x, y = self.feed[self.steps % len(self.feed)]
        # the last row never reaches the step: the first stands in for it
        x, y = x.at[-1].set(x[0]), y.at[-1].set(y[0])
        self.params, self.opt_state, loss = self.fn(
            self.params, self.opt_state, x, y)
        self.steps += 1
        return loss

    monkeypatch.setattr(train_step.Step, "__call__", partial)
    result = rehearsal.rehearse(monkeypatch, tmp_path,
                                BY_KIND["train_step"])
    assert result["correct"] is False
    bad = failed_checks(checks)
    assert "grad_sample_gap" in bad and "grad_norm_gap" in bad


@pytest.mark.parametrize("kind", ["serve_open", "serve_closed"])
def test_token_altered_where_it_is_produced(monkeypatch, tmp_path, checks,
                                            kind):
    real = serving.build_engine

    def tampered(ctx):
        engine, cfg = real(ctx)
        decode = engine._decode_jit

        def off_by_one(*args):
            tokens, *pools = decode(*args)
            return ((tokens + 1) % cfg["n_classes"], *pools)

        engine._decode_jit = off_by_one
        return engine, cfg

    monkeypatch.setattr(serving, "build_engine", tampered)
    result = rehearsal.rehearse(monkeypatch, tmp_path, BY_KIND[kind])
    assert result["correct"] is False
    assert "logit_gap_max" in failed_checks(checks)
