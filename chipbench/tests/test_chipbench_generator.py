"""The traffic generator: the six keys every mix has, the keys its loop
declares and hands on, and plans that stay the same seed for seed."""
import hashlib
import json

import pytest

from chipbench import generator, harness

SEED = 2**31 + 12345

# sha256 of each committed mix's ``kinds`` for SEED, as planned before
# loops could declare keys of their own
PLANS = {
    "mixed": (
        ["dense", "sparse"],
        "13acdce0bc31a202fd9a952ff8118a8478784271fae8fb299b5dd07c99f341f2"),
    "sparse": (
        ["sparse"],
        "c35020473aed1b4642cd726cad727b63fff2824ad68cedd7ffb73c7cbd890479"),
}


def traffic(name: str):
    return json.loads((harness.BENCH / "traffic" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name", sorted(PLANS))
def test_committed_mix_plans_as_before(name):
    p = generator.plan(traffic(name), SEED)
    names, digest = PLANS[name]
    assert p.kind_names == names and p.kinds.shape == (1024, 32)
    assert hashlib.sha256(p.kinds.tobytes()).hexdigest() == digest
    assert p.loop_params == {}


@pytest.fixture
def paced_loop(tmp_path, monkeypatch):
    """A loop ``paced`` that declares one key of its own."""
    (tmp_path / "paced.py").write_text('KEYS = frozenset({"rate_per_s"})\n')
    monkeypatch.setattr(generator, "LOOPS", tmp_path)
    return dict(traffic("sparse"), loop="paced")


def test_loop_declared_key_is_accepted_and_handed_on(paced_loop):
    p = generator.plan(dict(paced_loop, rate_per_s=12.5), SEED)
    assert p.loop == "paced" and p.loop_params == {"rate_per_s": 12.5}
    # the declared key changes nothing in the draws
    assert hashlib.sha256(p.kinds.tobytes()).hexdigest() == PLANS["sparse"][1]


@pytest.mark.parametrize("extra", [{}, {"rate_per_s": 1.0, "burst": 4},
                                   {"burst": 4}])
def test_loop_key_missing_or_undeclared_is_refused(paced_loop, extra):
    with pytest.raises(ValueError, match="traffic keys"):
        generator.plan(dict(paced_loop, **extra), SEED)


def test_closed_loop_refuses_a_key_of_another_loop():
    with pytest.raises(ValueError, match="unknown \\['rate_per_s'\\]"):
        generator.plan(dict(traffic("mixed"), rate_per_s=1.0), SEED)
