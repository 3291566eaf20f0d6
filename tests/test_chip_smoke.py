"""``chip_smoke.py`` on the CPU: it refuses to run without a chip, and its
Gateway trace and checks hold at a tiny size (reduced widths, short
histories) -- so a change that breaks the smoke run fails here before it
costs chip time."""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402  (the checkout root holds it)


def test_refuses_to_run_without_a_tpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "no TPU found" in err
    assert '"ok"' not in out


@pytest.mark.parametrize("arch", chip_smoke.SERVE_ARCHS)
def test_serve_phase_at_tiny_size(monkeypatch, capsys, arch):
    from repro.configs.base import get_config, reduced
    from repro.models.model import init_params

    for name, value in (("PREFILL_LEN", 32), ("HISTORY", 32),
                        ("INJECT_LEN", 8), ("CACHE_CAPACITY", 64),
                        ("N_USERS", 1600)):
        monkeypatch.setattr(chip_smoke, name, value)

    def tiny(arch, seed):
        cfg = reduced(get_config(arch), vocab=chip_smoke.N_ITEMS + 257)
        return cfg, init_params(cfg, jax.random.PRNGKey(seed),
                                dtype=jnp.float32)

    monkeypatch.setattr(chip_smoke, "init_params_for", tiny)
    chip_smoke.run_serve(arch, seed=0)
    out = capsys.readouterr().out
    assert "0 bytes differ" in out
    assert "served vs full prefill" in out
