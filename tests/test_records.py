"""Record-store persistence + the Mosaic block-size guard rails."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.mosaic_limits import (
    MAX_BLOCK_BYTES,
    block_ok,
    check_block,
    max_rows,
)


class TestRecords:
    def test_write_then_latest_roundtrip(self, tmp_path, monkeypatch):
        from apex_tpu import records

        monkeypatch.setattr(records, "RECORDS_DIR", str(tmp_path))
        p1 = records.write_record("unittest", {"x": 1}, backend="tpu")
        assert p1 and os.path.exists(p1)
        rec = records.latest_record("unittest", require_backend="tpu")
        assert rec["payload"] == {"x": 1}
        assert rec["backend"] == "tpu"
        assert rec["git_sha"]
        # cpu-backend records are filtered out by default
        records.write_record("unittest", {"x": 2}, backend="cpu")
        rec = records.latest_record("unittest", require_backend="tpu")
        assert rec["payload"] == {"x": 1}
        # unknown kind -> None, not an exception
        assert records.latest_record("nope") is None

    def test_legacy_record_without_kind_field(self, tmp_path, monkeypatch):
        """Early driver-captured chip records predate the top-level
        ``kind`` field; a missing ``kind`` matches through the exact
        ``{kind}_{stamp}`` filename shape instead of being dropped
        (ADVICE round 5) — without resurrecting the prefix cross-match
        bug ('tune' must not swallow 'tune_ln' files)."""
        from apex_tpu import records

        monkeypatch.setattr(records, "RECORDS_DIR", str(tmp_path))
        legacy = tmp_path / "headline_20260101T000000Z_aaaa.json"
        legacy.write_text(json.dumps({
            "utc": "20260101T000000Z", "backend": "tpu",
            "payload": {"v": "legacy"}}))
        rec = records.latest_record("headline", require_backend="tpu")
        assert rec is not None and rec["payload"] == {"v": "legacy"}
        # a newer record WITH the field still wins on recency
        records.write_record("headline", {"v": "new"}, backend="tpu")
        rec = records.latest_record("headline", require_backend="tpu")
        assert rec["payload"] == {"v": "new"}
        # kind-less file whose name is another kind plus suffix: no match
        other = tmp_path / "tune_ln_20260101T000000Z_aaaa.json"
        other.write_text(json.dumps({
            "utc": "20260101T000000Z", "backend": "tpu",
            "payload": {"v": "ln"}}))
        assert records.latest_record("tune", require_backend="tpu") is None

    def test_corrupt_record_skipped(self, tmp_path, monkeypatch):
        from apex_tpu import records

        monkeypatch.setattr(records, "RECORDS_DIR", str(tmp_path))
        records.write_record("k", {"ok": True}, backend="tpu")
        bad = tmp_path / "k_99999999T999999Z_dead.json"
        bad.write_text("{not json")
        rec = records.latest_record("k")
        assert rec is not None and rec["payload"] == {"ok": True}

    def test_corrupt_record_skip_emits_structured_event(
            self, tmp_path, monkeypatch):
        """A corrupt JSON line is skipped WITH a telemetry event +
        counter (never silently): the bench-record analog of
        latest_valid's corrupt_checkpoint record."""
        from apex_tpu import records, telemetry

        telemetry.reset()
        monkeypatch.setattr(records, "RECORDS_DIR", str(tmp_path))
        sink = telemetry.InMemorySink()
        telemetry.registry().add_sink(sink)
        records.write_record("k", {"ok": True}, backend="tpu")
        (tmp_path / "k_99999999T999999Z_dead.json").write_text("{not json")
        assert records.latest_record("k")["payload"] == {"ok": True}
        reg = telemetry.registry()
        assert reg.counter("records_corrupt_skipped").value() == 1.0
        ev = [e for e in sink.events
              if e["event"] == "record_corrupt_skipped"]
        assert len(ev) == 1
        assert ev[0]["file"] == "k_99999999T999999Z_dead.json"
        assert ev[0]["kind"] == "k" and "Error" in ev[0]["error"]
        telemetry.reset()

    def test_latest_record_empty_and_missing_directory(
            self, tmp_path, monkeypatch):
        from apex_tpu import records

        # empty directory: no matches, no exception
        monkeypatch.setattr(records, "RECORDS_DIR", str(tmp_path))
        assert records.latest_record("k") is None
        # directory that does not exist at all: same contract
        monkeypatch.setattr(records, "RECORDS_DIR",
                            str(tmp_path / "never_made"))
        assert records.latest_record("k") is None

    def test_latest_record_mixed_kind_files(self, tmp_path, monkeypatch):
        """A directory holding several kinds (+ non-record files): each
        kind resolves to ITS newest record, others never cross-match."""
        from apex_tpu import records

        monkeypatch.setattr(records, "RECORDS_DIR", str(tmp_path))
        records.write_record("headline", {"v": 1}, backend="tpu")
        records.write_record("attn", {"v": 2}, backend="tpu")
        records.write_record("attn", {"v": 3}, backend="tpu")
        records.write_record("resilience", {"v": 4}, backend="tpu")
        (tmp_path / "notes.txt").write_text("not a record")
        (tmp_path / "attn_README.json").write_text(
            json.dumps({"kind": "other", "utc": "99990101T000000Z",
                        "backend": "tpu", "payload": {"v": "imposter"}}))
        assert records.latest_record("headline")["payload"] == {"v": 1}
        assert records.latest_record("attn")["payload"] == {"v": 3}
        assert records.latest_record("resilience")["payload"] == {"v": 4}
        assert records.latest_record("notes") is None

    def test_kind_matches_exactly_never_by_prefix(
            self, tmp_path, monkeypatch):
        from apex_tpu import records

        monkeypatch.setattr(records, "RECORDS_DIR", str(tmp_path))
        records.write_record("tune", {"v": "real"}, backend="tpu")
        # kind match is exact against the record field: 'tune' must not
        # swallow 'tune_ln' records (filename-prefix cross-match bug)
        records.write_record("tune_ln", {"v": "ln"}, backend="tpu")
        rec = records.latest_record("tune", require_backend="tpu")
        assert rec["payload"] == {"v": "real"}

    def test_latest_uses_utc_field_and_uniquifier(
            self, tmp_path, monkeypatch):
        from apex_tpu import records

        monkeypatch.setattr(records, "RECORDS_DIR", str(tmp_path))
        # same second + SHA: write_record uniquifies to base.1.json,
        # which sorts lexicographically BEFORE base.json — the parsed
        # (utc, uniquifier) order must still pick the later write
        p0 = records.write_record("k", {"n": 0}, backend="tpu")
        p1 = records.write_record("k", {"n": 1}, backend="tpu")
        if p1.endswith(".1.json"):  # same-second collision: uniquified
            rec = records.latest_record("k")
            assert rec["payload"] == {"n": 1}, (p0, p1)
        # an older filename with a newer utc field wins
        old = tmp_path / "k_00000000T000000Z_aaaa.json"
        old.write_text(json.dumps({
            "kind": "k", "utc": "99990101T000000Z", "backend": "tpu",
            "captured": True, "payload": {"n": "future"}}))
        rec = records.latest_record("k")
        assert rec["payload"] == {"n": "future"}

    def test_same_second_writes_never_overwrite(self, tmp_path,
                                                monkeypatch):
        """The filename stamp is 1-second resolution; same-second
        writes must land in DISTINCT files (monotonic disambiguator +
        O_EXCL claim), with the later write winning recency."""
        from apex_tpu import records

        monkeypatch.setattr(records, "RECORDS_DIR", str(tmp_path))
        # freeze the stamp so every write collides on the base name
        monkeypatch.setattr(records.time, "strftime",
                            lambda *a: "20260101T000000Z")
        paths = [records.write_record("k", {"n": i}, backend="tpu")
                 for i in range(3)]
        assert None not in paths
        assert len(set(paths)) == 3               # three distinct files
        assert len(list(tmp_path.iterdir())) == 3  # nothing overwritten
        # the monotonic disambiguator orders same-second writes: the
        # LAST write is the latest record
        rec = records.latest_record("k")
        assert rec["payload"] == {"n": 2}

    def test_fsync_fault_absorbed_claim_never_lost(self, tmp_path,
                                                   monkeypatch):
        """The directory fsync after the O_EXCL claim (site
        ``record_fsync``) is part of the retried attempt: a transient
        failure there unlinks the claim and rewrites — one well-formed
        record, no truncated ghost, disambiguator semantics intact."""
        import json

        from apex_tpu import records
        from apex_tpu.resilience import faults

        monkeypatch.setattr(records, "RECORDS_DIR", str(tmp_path))
        monkeypatch.setattr(records.time, "strftime",
                            lambda *a: "20260101T000000Z")
        with faults.inject(io_errors={"record_fsync": frozenset({0})}):
            p1 = records.write_record("k", {"n": 1}, backend="tpu")
        assert p1 is not None
        files = list(tmp_path.iterdir())
        assert len(files) == 1                    # no ghost from attempt 1
        assert json.loads(files[0].read_text())["payload"] == {"n": 1}
        # the retried claim reused the UNDISAMBIGUATED base name (the
        # failed attempt unlinked its claim), so a same-second
        # follow-up still orders after it
        p2 = records.write_record("k", {"n": 2}, backend="tpu")
        assert p2 != p1
        assert records.latest_record("k")["payload"] == {"n": 2}
        # a permanently failing fsync behaves like any dead disk:
        # None returned, nothing left behind
        with faults.inject(io_permanent_from={"record_fsync": 0}):
            assert records.write_record("k2", {"n": 3}) is None
        assert not [f for f in tmp_path.iterdir()
                    if f.name.startswith("k2_")]

    def test_claim_is_exclusive_not_exists_check(self, tmp_path,
                                                 monkeypatch):
        """A pre-existing file with the exact base name (the TOCTOU
        partner in a cross-process race) is never clobbered: the claim
        is O_CREAT|O_EXCL, so the writer falls through to a
        disambiguated name."""
        import json

        from apex_tpu import records

        monkeypatch.setattr(records, "RECORDS_DIR", str(tmp_path))
        monkeypatch.setattr(records.time, "strftime",
                            lambda *a: "20260101T000000Z")
        sha = records._git_sha()
        victim = tmp_path / f"k_20260101T000000Z_{sha}.json"
        victim.write_text(json.dumps({
            "kind": "k", "utc": "20260101T000000Z", "backend": "tpu",
            "captured": True, "payload": {"n": "first"}}))
        p = records.write_record("k", {"n": "second"}, backend="tpu")
        assert p is not None and p != str(victim)
        # the racing writer's record is intact...
        assert json.loads(victim.read_text())["payload"] == {"n": "first"}
        # ...and the new write still wins recency via the disambiguator
        assert records.latest_record("k")["payload"] == {"n": "second"}


class TestPruneRecords:
    """``records.prune_records`` — keep-last-k retention for record
    kinds a failure loop can write without bound (flight bundles)."""

    def _stamped_writer(self, monkeypatch):
        from apex_tpu import records

        tick = iter(range(100))
        monkeypatch.setattr(
            records.time, "strftime",
            lambda *a: f"20260101T0000{next(tick):02d}Z")

    def test_keeps_newest_k_by_recency(self, tmp_path, monkeypatch):
        from apex_tpu import records

        monkeypatch.setattr(records, "RECORDS_DIR", str(tmp_path))
        self._stamped_writer(monkeypatch)
        paths = [records.write_record("flightrec", {"n": i})
                 for i in range(6)]
        removed = records.prune_records("flightrec", keep=2)
        assert sorted(removed) == sorted(paths[:4])
        # latest_record still finds the newest bundle
        assert records.latest_record(
            "flightrec", require_backend=None)["payload"] == {"n": 5}

    def test_other_kinds_and_prefix_kinds_untouched(self, tmp_path,
                                                    monkeypatch):
        from apex_tpu import records

        monkeypatch.setattr(records, "RECORDS_DIR", str(tmp_path))
        self._stamped_writer(monkeypatch)
        for i in range(3):
            records.write_record("flight", {"n": i})        # prefix kind
            records.write_record("flightrec", {"n": i})
            records.write_record("resilience", {"n": i})
        records.prune_records("flightrec", keep=1)
        names = os.listdir(tmp_path)
        assert sum(n.startswith("flightrec_") for n in names) == 1
        assert sum(n.startswith("flight_") for n in names) == 3
        assert sum(n.startswith("resilience_") for n in names) == 3
        assert records.latest_record(
            "flight", require_backend=None)["payload"] == {"n": 2}

    def test_keep_nonpositive_and_missing_dir_are_noops(self, tmp_path,
                                                        monkeypatch):
        from apex_tpu import records

        monkeypatch.setattr(records, "RECORDS_DIR", str(tmp_path))
        self._stamped_writer(monkeypatch)
        for i in range(3):
            records.write_record("flightrec", {"n": i})
        assert records.prune_records("flightrec", keep=0) == []
        assert len(os.listdir(tmp_path)) == 3
        monkeypatch.setattr(records, "RECORDS_DIR",
                            str(tmp_path / "nonexistent"))
        assert records.prune_records("flightrec", keep=1) == []

    def test_corrupt_files_left_in_place(self, tmp_path, monkeypatch):
        from apex_tpu import records

        monkeypatch.setattr(records, "RECORDS_DIR", str(tmp_path))
        self._stamped_writer(monkeypatch)
        records.write_record("flightrec", {"n": 0})
        records.write_record("flightrec", {"n": 1})
        corrupt = tmp_path / "flightrec_20251231T000000Z_dead.json"
        corrupt.write_text("{not json")
        records.prune_records("flightrec", keep=1)
        assert corrupt.exists()                  # evidence stays
        assert records.latest_record(
            "flightrec", require_backend=None)["payload"] == {"n": 1}

    def test_current_second_is_never_pruned(self, tmp_path, monkeypatch):
        # deleting a record stamped "now" would free its O_EXCL claim
        # name for a same-second re-claim with a lower uniquifier,
        # breaking latest_record's write-order tiebreak
        from apex_tpu import records

        monkeypatch.setattr(records, "RECORDS_DIR", str(tmp_path))
        monkeypatch.setattr(records.time, "strftime",
                            lambda *a: "20260101T000000Z")
        paths = [records.write_record("flightrec", {"n": i})
                 for i in range(4)]
        assert records.prune_records("flightrec", keep=1) == []
        assert all(os.path.exists(p) for p in paths)
        assert records.latest_record(
            "flightrec", require_backend=None)["payload"] == {"n": 3}


class TestMosaicLimits:
    def test_refused_blocks_rejected(self):
        # blocks of 4 MiB the installed compiler refuses (VMEM), seen in
        # compiles for a described v5e (ops/mosaic_limits.py)
        assert not block_ok(256, 4096, 4)     # LN tile
        assert not block_ok(1024, 1024, 4)    # LN tile
        assert not block_ok(8192, 128, 4)     # engine tile
        # what the same compiler takes stays allowed, the 2048- and
        # 4096-row engine tiles of the retired sublane cap included
        assert block_ok(2048, 128, 4)
        assert block_ok(4096, 128, 4)
        assert block_ok(1024, 128, 2)         # flash 1024 blocks bf16
        assert block_ok(512, 128, 4)          # engine default tile
        assert block_ok(128, 4096, 4)         # LN tile under 4 MiB

    def test_max_rows_is_safe_and_aligned(self):
        for cols in (128, 1024, 4096, 30528):
            r = max_rows(cols, 4)
            assert r % 8 == 0 and r >= 8
            assert block_ok(r, cols, 4) or r == 8

    def test_check_block_raises_with_guidance(self):
        with pytest.raises(ValueError, match="compiler refuses"):
            check_block(8192, 128, 4, what="engine tile")

    def test_engine_refuses_oversized_tile(self):
        from apex_tpu.multi_tensor.engine import fused_elementwise

        buf = jnp.zeros((8192 * 128,), jnp.float32)
        with pytest.raises(ValueError, match="compiler refuses"):
            fused_elementwise(
                lambda ins, s, t: [ins[0] * 2.0], [buf],
                num_outputs=1, tile_rows=8192, impl="interpret")

    def test_row_tile_stays_under_the_limit(self):
        from apex_tpu.ops._tiling import row_tile

        rng = np.random.RandomState(0)
        for _ in range(200):
            rows = int(rng.randint(1, 1 << 14))
            cols = int(rng.choice([128, 512, 1024, 4096, 8192, 32768]))
            # adversarial caller: huge cap/budget must still be clamped
            t = row_tile(rows, cols, cap=1 << 20, budget=1 << 30)
            if t is not None:
                assert block_ok(t, cols, 4), (rows, cols, t)
        assert MAX_BLOCK_BYTES == 4 * 1024 * 1024
