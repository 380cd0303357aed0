"""The comparisons that decide `correct`."""
import numpy as np
import pytest

from perfbench.harness import compare


def test_logits_agree_is_relative_to_the_scale():
    ref = np.array([[10.0, 0.0, -10.0]])
    assert compare.logits_agree(ref + 0.04, ref, 0.005)[0]
    ok, err = compare.logits_agree(ref + 0.1, ref, 0.005)
    assert not ok and err == pytest.approx(0.01, rel=1e-4)
    assert not compare.logits_agree(ref * np.nan, ref, 0.5)[0]


def test_served_tokens_judged_by_margin_only_at_served_positions():
    short = np.zeros((2, 9))
    short[0, 0] = 5.0          # a prompt position: not judged
    short[1, 6] = 0.2          # row 1: prompt 4, total 8 -> columns 3..6
    ok, worst = compare.served_within_margin(short, [3, 4], [6, 8], 0.3)
    assert ok and worst == pytest.approx(0.2)
    assert not compare.served_within_margin(short, [3, 4], [6, 8], 0.1)[0]
    short[0, 8] = 9.0          # beyond the reply: not judged
    assert compare.served_within_margin(short, [3, 4], [6, 8], 0.3)[0]
    short[0, 4] = np.inf
    assert not compare.served_within_margin(short, [3, 4], [6, 8], 0.3)[0]


@pytest.mark.parametrize("first,window,ok", [
    (8.0, [7.0, 6.0], True), (8.0, [9.0, 8.5], False),
    (8.0, [7.0, float("nan")], False), (8.0, [], False)])
def test_losses_fall(first, window, ok):
    assert compare.losses_fall(first, window) is ok


def test_replicas_identical_sees_one_flipped_bit():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    rep = jax.device_put(jnp.arange(8.0), NamedSharding(mesh, PartitionSpec()))
    assert compare.replicas_identical([rep]) == (True, 0)
    shards = [jax.device_put(jnp.arange(8.0) + (i == 2) * 1e-6, d)
              for i, d in enumerate(jax.devices()[:4])]
    bad = jax.make_array_from_single_device_arrays(
        (8,), NamedSharding(mesh, PartitionSpec()), shards)
    assert compare.replicas_identical([rep, bad]) == (False, 1)
