"""Fleet driver + cross-experiment executable sharing (DESIGN.md §12).

Covers the PR-8 contracts:
  * sweep syntax: comma lists / grid expansion / loud unknown-path errors;
  * ``fed.k_grid0``: pinned quantize anchor collapses a k0 sweep onto one
    bucket signature (and validates loudly);
  * registry counters: a registry hit from another experiment is a
    ``shared_count``, never a local compile — and the adopted executable
    is the SAME object (bitwise-shared program);
  * key isolation: transport codecs / backends / mesh slices never
    collide;
  * the driver: packed == serial results, consolidated CSV/leaderboard.
"""
import numpy as np
import pytest

from repro.api import ExperimentSpec, build, expand_sweep, sweep_grid
from repro.api.spec import SpecValidationError
from repro.api.sweep import spec_program_key
from repro.core.engine.round import ExecutableRegistry


def _base(**kw):
    ov = ["data.kind=paper", "data.task=femnist", "data.clients=8",
          "data.samples_per_client=8", "fed.clients_per_round=4",
          "fed.rounds=2", "fed.batch_size=4", "fed.bucket_rounds=2",
          "fed.eta0=0.3"]
    ov += [f"{k}={v}" for k, v in kw.items()]
    return ExperimentSpec().with_overrides(*ov)


# ---------------------------------------------------------------------------
# sweep syntax
# ---------------------------------------------------------------------------

class TestSweepSyntax:
    def test_grid_cross_product(self):
        pts = expand_sweep("fed.k0=2,4,8", "transport.name=none,int8",
                           base=_base())
        assert len(pts) == 6
        labels = {p.label for p in pts}
        assert "k0=2|name=none" in labels and "k0=8|name=int8" in labels
        k0s = sorted({p.spec.fed.k0 for p in pts})
        assert k0s == [2, 4, 8]

    def test_single_value_axis(self):
        pts = expand_sweep("fed.k0=4", base=_base())
        assert len(pts) == 1 and pts[0].spec.fed.k0 == 4

    def test_unknown_paths_aggregate_loudly(self):
        with pytest.raises(SpecValidationError) as ei:
            expand_sweep("fed.nope=1,2", "bogus.k0=1", base=_base())
        msg = str(ei.value)
        assert "fed.nope" in msg and "bogus" in msg

    def test_bad_value_reports_point_label(self):
        with pytest.raises(SpecValidationError) as ei:
            expand_sweep("transport.name=int8,not_a_codec", base=_base())
        assert "not_a_codec" in str(ei.value)

    def test_grid_labels_unique_per_point(self):
        grid = sweep_grid(["fed.k0=2,4", "fed.eta0=0.1,0.2"])
        labels = [label for _, label in grid]
        assert len(labels) == len(set(labels)) == 4

    def test_comma_list_coerces_on_tuple_field(self):
        spec = ExperimentSpec().with_overrides("sampler.cohort=0,1,2")
        assert spec.sampler.cohort == (0, 1, 2)

    def test_comma_list_on_scalar_field_hints_sweep(self):
        with pytest.raises(SpecValidationError) as ei:
            ExperimentSpec().with_overrides("fed.k0=2,4,8")
        assert "sweep" in str(ei.value)


# ---------------------------------------------------------------------------
# k_grid0
# ---------------------------------------------------------------------------

class TestKGrid0:
    def test_anchor_snaps_k0_range_to_one_k(self):
        from repro.configs.base import FedConfig
        from repro.core.schedules import DecayController
        ks = set()
        for k0 in (12, 14, 15, 16):
            fed = FedConfig(k0=k0, k_quantize=True, k_grid0=16,
                            k_schedule="fixed")
            ks.add(DecayController(fed).k_for_round(1))
        assert ks == {16}

    def test_none_anchor_keeps_k0_grid(self):
        from repro.configs.base import FedConfig
        from repro.core.schedules import DecayController
        fed = FedConfig(k0=12, k_quantize=True, k_schedule="fixed")
        assert DecayController(fed).k_for_round(1) == 12

    def test_validation_requires_quantize(self):
        with pytest.raises(SpecValidationError) as ei:
            _base(**{"fed.k_grid0": 16}).validate()
        assert "k_quantize" in str(ei.value)

    def test_validation_rejects_nonpositive(self):
        with pytest.raises(SpecValidationError):
            _base(**{"fed.k_quantize": "true",
                     "fed.k_grid0": 0}).validate()

    def test_spec_roundtrip(self):
        spec = _base(**{"fed.k_quantize": "true", "fed.k_grid0": 16})
        assert ExperimentSpec.from_json(spec.to_json()) == spec


# ---------------------------------------------------------------------------
# registry sharing + counters
# ---------------------------------------------------------------------------

class TestRegistrySharing:
    def test_shared_hit_not_double_counted(self):
        reg = ExecutableRegistry()
        spec = _base().validate()
        a = build(spec, registry=reg)
        b = build(spec, registry=reg)
        a.run()
        b.run()
        assert a.trainer.compile_count == 1
        assert a.trainer.shared_count == 0
        # B adopted A's executable: a shared_count, NOT a local compile
        assert b.trainer.compile_count == 0
        assert b.trainer.shared_count == 1
        assert reg.compile_count == 1

    def test_shared_executable_is_same_object(self):
        reg = ExecutableRegistry()
        a = build(_base(), registry=reg)
        b = build(_base(), registry=reg)
        a.run()
        b.run()
        ex_a = list(a.trainer.engine._executables.values())
        ex_b = list(b.trainer.engine._executables.values())
        assert len(ex_a) == len(ex_b) == 1
        assert ex_a[0] is ex_b[0]

    def test_same_k_bucket_different_k0_shares(self):
        # the satellite contract: two points differing only in fed.k0,
        # snapped into one K grid bucket via k_grid0, share bitwise
        reg = ExecutableRegistry()
        exps = []
        for k0 in (15, 16):
            spec = _base(**{"fed.k0": k0, "fed.k_quantize": "true",
                            "fed.k_grid0": 16})
            exps.append(build(spec, registry=reg))
        for e in exps:
            e.run()
        assert exps[0].trainer.compile_count == 1
        assert exps[1].trainer.compile_count == 0
        assert exps[1].trainer.shared_count == 1
        a = list(exps[0].trainer.engine._executables.values())[0]
        b = list(exps[1].trainer.engine._executables.values())[0]
        assert a is b

    def test_transport_codecs_do_not_collide(self):
        # same shapes, different traced program -> distinct registry keys
        reg = ExecutableRegistry()
        for name in ("none", "int8"):
            e = build(_base(**{"transport.name": name}), registry=reg)
            e.run()
            assert e.trainer.shared_count == 0
        assert reg.compile_count == 2

    def test_transport_codecs_do_not_collide_mesh(self):
        reg = ExecutableRegistry()
        for name in ("none", "int8"):
            e = build(_base(**{"transport.name": name,
                               "backend.name": "mesh"}), registry=reg)
            e.run()
            assert e.trainer.shared_count == 0
        assert reg.compile_count == 2

    def test_registry_requires_program_key(self):
        from repro.core.engine.round import RoundEngine
        with pytest.raises(ValueError, match="program_key"):
            RoundEngine(lambda p, b: 0.0, registry=ExecutableRegistry())

    def test_private_registry_back_compat(self):
        e = build(_base())
        e.run()
        assert e.trainer.compile_count == 1
        assert e.trainer.shared_count == 0
        assert len(e.trainer.engine._executables) == 1

    def test_program_key_distinguishes_codec_and_backend(self):
        k_none = spec_program_key(_base())
        k_int8 = spec_program_key(_base(**{"transport.name": "int8"}))
        k_mesh = spec_program_key(_base(**{"backend.name": "mesh"}))
        assert len({k_none, k_int8, k_mesh}) == 3
        # signature-only knobs do NOT split the program key
        assert spec_program_key(_base(**{"fed.k0": 2})) == k_none

    def test_single_flight_under_concurrency(self):
        import threading
        reg = ExecutableRegistry()
        built = []

        def build_fn():
            built.append(1)
            return object()

        results = []
        threads = [threading.Thread(
            target=lambda: results.append(reg.get_or_build(("k",), build_fn)))
            for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(built) == 1
        assert len({id(r[0]) for r in results}) == 1
        assert sum(1 for r in results if r[1]) == 1


# ---------------------------------------------------------------------------
# backend slices
# ---------------------------------------------------------------------------

class CarveMesh:
    """Duck-typed mesh with a device grid, for carve_submeshes tests."""

    def __init__(self, devices, axis_names):
        self.devices = np.asarray(devices)
        self.axis_names = tuple(axis_names)

    @property
    def shape(self):
        return dict(zip(self.axis_names, self.devices.shape))


class TestFleetSlices:
    def test_carve_splits_largest_axis(self):
        from repro.core.engine.backends.mesh import carve_submeshes
        mesh = CarveMesh(np.arange(8).reshape(4, 2), ("data", "model"))
        subs = carve_submeshes(mesh, 4)
        assert len(subs) == 4
        assert all(s.devices.shape == (1, 2) for s in subs)
        assert all(s.axis_names == ("data", "model") for s in subs)
        got = sorted(d for s in subs for d in s.devices.flat)
        assert got == list(range(8))

    def test_carve_nondivisible_takes_largest_divisor(self):
        from repro.core.engine.backends.mesh import carve_submeshes
        mesh = CarveMesh(np.arange(6).reshape(6, 1), ("data", "model"))
        subs = carve_submeshes(mesh, 4)     # 4 ∤ 6 -> 3 slices of 2
        assert len(subs) == 3
        assert all(s.devices.shape == (2, 1) for s in subs)

    def test_carve_single_device_returns_self(self):
        from repro.core.engine.backends.mesh import carve_submeshes
        mesh = CarveMesh(np.arange(1).reshape(1, 1), ("data", "model"))
        assert carve_submeshes(mesh, 4) == [mesh]

    def test_local_fleet_slices_fresh_instances(self):
        from repro.core.engine.backends.local import LocalBackend
        be = LocalBackend()
        slices = be.fleet_slices(3)
        assert len(slices) == 3
        assert len({id(s) for s in slices}) == 3
        assert all(isinstance(s, LocalBackend) for s in slices)

    def test_mesh_fleet_slices_cycles_and_preserves_config(self):
        from repro.core.engine.backends.mesh import MeshBackend
        mesh = CarveMesh(np.arange(2).reshape(2, 1), ("data", "model"))
        be = MeshBackend.__new__(MeshBackend)
        be.mesh = mesh
        be.strategy = "parallel"
        be.client_axes = ("data",)
        be.groups = 1
        be.param_specs = None
        be.acc_dtype = np.float32
        be.reduce = "flat"
        slices = be.fleet_slices(4)          # 2 sub-meshes cycled over 4
        assert len(slices) == 4
        assert slices[0].mesh.devices.tolist() == slices[2].mesh.devices.tolist()
        assert all(s.strategy == "parallel" and s.reduce == "flat"
                   for s in slices)


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------

class TestFleetDriver:
    def _points(self):
        from repro.api.sweep import expand_sweep
        from repro.launch.fleet import share_k_grid
        return share_k_grid(
            expand_sweep("fed.k0=15,16", base=_base()))

    def test_packed_matches_serial_and_shares(self):
        from repro.launch.fleet import run_fleet
        packed = run_fleet(points=self._points(), packed=True,
                           verbose=False)
        serial = run_fleet(points=self._points(), packed=False,
                           verbose=False)
        assert packed.compile_count == 1          # one bucket signature
        assert serial.compile_count == 1
        assert packed.shared_count == 1
        p = {r.label: r for r in packed.points}
        s = {r.label: r for r in serial.points}
        assert set(p) == set(s)
        for label in p:
            assert p[label].final_loss == s[label].final_loss

    def test_leaderboard_and_csv(self, tmp_path):
        from repro.launch.fleet import run_fleet, CSV_FIELDS
        res = run_fleet(points=self._points(), packed=False, verbose=False)
        board = res.leaderboard()
        assert "k0=15" in board and "k0=16" in board
        out = tmp_path / "fleet.csv"
        res.to_csv(str(out))
        import csv
        with open(out) as f:
            rows = list(csv.DictReader(f))
        assert len(rows) == 2
        assert tuple(rows[0]) == CSV_FIELDS
        assert {r["label"] for r in rows} == {"k0=15", "k0=16"}

    def test_empty_sweep_raises(self):
        from repro.launch.fleet import run_fleet
        with pytest.raises((ValueError, SpecValidationError)):
            run_fleet(points=[], packed=True)

    def test_share_k_grid_pins_max_anchor(self):
        from repro.launch.fleet import share_k_grid
        pts = share_k_grid(expand_sweep("fed.k0=4,8,6", base=_base()))
        assert all(p.spec.fed.k_grid0 == 8 for p in pts)
        assert all(p.spec.fed.k_quantize for p in pts)

    def test_train_cli_sweep_smoke(self, capsys, tmp_path):
        from repro.launch import train
        csv_path = str(tmp_path / "sweep.csv")
        train.main([
            "--rounds", "2",
            "--set", "data.clients=8", "--set", "fed.clients_per_round=4",
            "--set", "fed.batch_size=4",
            "--set", "data.samples_per_client=8",
            "--set", "data.seq_len=16",
            "--set", "fed.k_schedule=fixed",
            "--sweep", "fed.k0=7,8", "--share-k-grid",
            "--sweep-csv", csv_path])
        out = capsys.readouterr().out
        assert "fleet:" in out and "k0=7" in out
        import os
        assert os.path.exists(csv_path)
