"""The T-sharded path over four (virtual) devices: a small sharded cell
passes the comparison that decides ``correct``, and fails it once the halo
exchange between chips is left out."""
import jax
import pytest

from chipbench_helpers import on_cpu, run_small, small_root


@pytest.fixture
def harness_env(monkeypatch):
    restore = on_cpu(monkeypatch)
    yield
    restore()


def test_halo_exchange_left_out_is_not_correct(tmp_path, monkeypatch,
                                               harness_env):
    """The T-sharded solve over four devices with every ppermute handing
    back its own input: the program's own residual, which shares the
    exchange, can read converged; the reference does not."""
    if jax.device_count() < 4:
        pytest.fail("needs 4 devices (tests/conftest.py sets 8 on the CPU)")
    root = small_root(tmp_path, lattice=(4, 4, 4, 8), t_shards=4)
    monkeypatch.setattr(jax.lax, "ppermute", lambda x, *a, **k: x)
    out = run_small(root, "small.cell", seconds=0.0)
    assert out["correct"] is False
    assert out["checks"]["residual_max"]["value"] > 1e-6


def test_sharded_small_cell_is_correct(tmp_path, harness_env):
    """The same sharded cell with the exchange in place passes."""
    if jax.device_count() < 4:
        pytest.fail("needs 4 devices (tests/conftest.py sets 8 on the CPU)")
    root = small_root(tmp_path, lattice=(4, 4, 4, 8), t_shards=4)
    out = run_small(root, "small.cell", seconds=0.0)
    assert out["correct"] is True and out["device"]["count"] == 4
