"""The jax.profiler trace hook (tpu_gossip/utils/profiling.py; SURVEY.md §5.1)."""

import jax
import jax.numpy as jnp
import pytest

from tpu_gossip.utils.profiling import trace


@pytest.mark.slow  # spins up the real xplane writer; the no-op contract
# below keeps the trace hook in tier-1
def test_trace_writes_profile_artifacts(tmp_path):
    log_dir = tmp_path / "trace"
    with trace(log_dir):
        x = jax.jit(lambda a: a * 2 + 1)(jnp.arange(128))
        float(jnp.sum(x))
    # jax writes plugins/profile/<run>/*.xplane.pb under the log dir
    artifacts = list(log_dir.rglob("*.xplane.pb"))
    assert artifacts, f"no trace artifacts under {log_dir}"


def test_trace_disabled_is_noop(tmp_path):
    with trace(None):
        pass
    with trace(""):
        pass
    assert list(tmp_path.iterdir()) == []
