"""The synthetic corpus and the checkpoint store the bench index is kept in."""
import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt
from repro.data import insert_stream, make_clustered


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def test_clustered_corpus_shapes():
    v, a, c = make_clustered(jax.random.PRNGKey(0), 200, 16, n_clusters=4)
    assert v.shape == (200, 16) and c.shape == (4, 16)
    drift0 = insert_stream(jax.random.PRNGKey(1), c, 50, drift=0.0)
    assert drift0.shape == (50, 16)


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_bf16(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.bfloat16).reshape(2, 3),
            "b": [jnp.float32(1.5), jnp.int32(7)],
            "c": {"d": jnp.ones((4,), jnp.int8)}}
    ckpt.save(tmp_path, 3, tree)
    step, out = ckpt.load_latest(tmp_path, tree)
    assert step == 3
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x, np.float32)
                                      if x.dtype == jnp.bfloat16 else x,
                                      np.asarray(y, np.float32)
                                      if y.dtype == jnp.bfloat16 else y)


def test_checkpoint_gc_and_latest(tmp_path):
    tree = {"x": jnp.zeros((2,))}
    for s in (1, 2, 3, 4, 5):
        ckpt.save(tmp_path, s, tree, keep=2)
    dirs = sorted(d.name for d in tmp_path.iterdir() if d.is_dir())
    assert dirs == ["step_00000004", "step_00000005"]
    assert ckpt.latest_step(tmp_path) == 5


def test_checkpoint_atomic_torn_commit(tmp_path):
    tree = {"x": jnp.zeros((2,))}
    ckpt.save(tmp_path, 1, tree)
    # simulate a torn commit: LATEST points at a missing dir
    (tmp_path / "LATEST").write_text("step_00000099")
    assert ckpt.latest_step(tmp_path) is None
