"""Regression tests for the serving-path launch/engine fixes:

* ``launch/mesh.py`` must construct the smoke and production meshes on the
  installed jax.
* ``launch/hillclimb.py`` must append (not clobber) the forced-host-devices
  flag to a user-set ``XLA_FLAGS``, and must keep its module docstring.
* ``serve/engine.py::_install_prefix`` must raise on an unmergeable prefill
  cache leaf instead of silently serving from the zeroed preallocation.
* ``launch/serve.py::apply_tuned_schedules`` must warn-and-skip invalid
  schedule entries (unknown kernels, non-integer block values) while still
  applying every valid one — a stale schedules file must not reject the
  tuned schedules that do apply.
"""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")


def test_smoke_mesh_constructs_on_installed_jax():
    from repro.launch.mesh import smoke_mesh

    m = smoke_mesh(1, 1)
    assert m.axis_names == ("data", "model")
    assert m.shape == {"data": 1, "model": 1}


def test_production_meshes_and_hillclimb_flags_subprocess():
    """Both production meshes (carve + exact branch) need 512 host devices,
    which must be forced before the first jax import — so this runs in a
    subprocess.  The same subprocess checks hillclimb's import-time env
    handling: the user's preexisting XLA_FLAGS survive with the host-device
    flag appended, and the module has a real docstring."""
    script = r"""
import os
assert os.environ["XLA_FLAGS"] == "--xla_cpu_use_thunk_runtime=false"
import repro.launch.hillclimb as hc
assert hc.__doc__ and "hillclimbing" in hc.__doc__, "module docstring lost"
flags = os.environ["XLA_FLAGS"]
assert "--xla_cpu_use_thunk_runtime=false" in flags, flags
assert "--xla_force_host_platform_device_count=512" in flags, flags

from repro.launch.mesh import make_production_mesh, smoke_mesh
m = smoke_mesh(2, 2)
assert m.shape == {"data": 2, "model": 2}
single = make_production_mesh()                 # 256 of 512: carve branch
assert single.shape == {"data": 16, "model": 16}
multi = make_production_mesh(multi_pod=True)    # 512 exact: make_mesh branch
assert multi.shape == {"pod": 2, "data": 16, "model": 16}
print("MESHES_OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_cpu_use_thunk_runtime=false"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, f"stdout={out.stdout}\nstderr={out.stderr}"
    assert "MESHES_OK" in out.stdout


def test_install_prefix_rejects_unmergeable_leaf():
    import jax.numpy as jnp

    from repro.serve.engine import _install_prefix

    # healthy tree: prefill (shorter seq dim) pads into the preallocation
    dst = {"k": jnp.zeros((1, 4, 32, 8)), "len": jnp.array([5])}
    src = {"k": jnp.ones((1, 4, 5, 8)), "len": jnp.array([5])}
    merged = _install_prefix(dst, src, 32)
    assert merged["k"].shape == (1, 4, 32, 8)
    np.testing.assert_array_equal(np.asarray(merged["k"][:, :, :5]), 1.0)
    np.testing.assert_array_equal(np.asarray(merged["k"][:, :, 5:]), 0.0)

    # prefill leaf longer than the preallocation: must raise, not silently
    # keep the zeroed destination
    bad = {"k": jnp.ones((1, 4, 64, 8)), "len": jnp.array([5])}
    with pytest.raises(ValueError, match="cannot merge prefill cache leaf"):
        _install_prefix(dst, bad, 32)

    # rank mismatch: also unmergeable
    bad_rank = {"k": jnp.ones((4, 5, 8)), "len": jnp.array([5])}
    with pytest.raises(ValueError, match="cannot merge prefill cache leaf"):
        _install_prefix(dst, bad_rank, 32)


class TestApplyTunedSchedules:
    def _apply(self, tmp_path, schedules, caplog):
        import json
        import logging

        from repro.configs.base import get_config
        from repro.launch.serve import apply_tuned_schedules

        path = tmp_path / "kernel_schedules.json"
        path.write_text(json.dumps(schedules))
        cfg = get_config("internlm2_1_8b").reduced()
        with caplog.at_level(logging.WARNING, logger="repro.launch.serve"):
            return apply_tuned_schedules(cfg, str(path))

    def test_valid_entries_apply(self, tmp_path, caplog):
        cfg, overrides = self._apply(
            tmp_path,
            {"attention": {"block_q": 64}, "ssd": {"chunk": 16}}, caplog)
        assert overrides == {"attn_q_chunk": 64, "ssd_chunk": 16}
        assert cfg.attn_q_chunk == 64 and cfg.ssd_chunk == 16
        assert not caplog.records

    def test_unknown_kernel_warns_and_skips(self, tmp_path, caplog):
        cfg, overrides = self._apply(
            tmp_path,
            {"attention": {"block_q": 64},
             "flashfusion": {"block_q": 128}}, caplog)
        # the valid entry still applies; the unknown one is skipped loudly
        assert overrides == {"attn_q_chunk": 64}
        assert cfg.attn_q_chunk == 64
        assert any("flashfusion" in r.message and "skipping" in r.message
                   for r in caplog.records)

    def test_non_int_blocks_warn_and_skip(self, tmp_path, caplog):
        cfg, overrides = self._apply(
            tmp_path,
            {"attention": {"block_q": "64"},    # strings are not block sizes
             "ssd": {"chunk": True},            # neither are JSON booleans
             "other": 64},                      # nor non-object params
            caplog)
        assert overrides == {}
        assert len(caplog.records) == 3
        assert all("skipping" in r.message for r in caplog.records)

    def test_non_object_file_raises(self, tmp_path, caplog):
        with pytest.raises(ValueError, match="expected a JSON object"):
            self._apply(tmp_path, ["attention"], caplog)
