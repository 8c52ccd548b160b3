"""utils/logging coverage (ISSUE 1 satellite): RankInfoFormatter with
and without parallel_state, get_logger child-namespacing,
set_logging_level round-trip, and the print_rank_0 backendless guard."""

import logging as pylogging

import jax

import apex_tpu.utils.logging as alog


def _format(fmt="%(rank_info)s|%(message)s", msg="hello"):
    formatter = alog.RankInfoFormatter(fmt)
    record = pylogging.LogRecord(
        "apex_tpu.test", pylogging.INFO, __file__, 1, msg, None, None)
    return formatter.format(record)


class TestRankInfoFormatter:
    def test_without_parallel_state(self):
        # conftest: single process on the virtual CPU mesh
        out = _format()
        assert out.endswith("|hello")
        assert "[host 0/1]" in out

    def test_with_parallel_state(self, monkeypatch):
        from apex_tpu.transformer import parallel_state

        monkeypatch.setattr(
            parallel_state, "model_parallel_is_initialized", lambda: True)
        monkeypatch.setattr(
            parallel_state, "get_rank_info", lambda: "(tp 0/2, pp 1/2)")
        out = _format()
        assert "(tp 0/2, pp 1/2)" in out
        assert out.endswith("|hello")

    def test_survives_backendless_jax(self, monkeypatch):
        def boom():
            raise RuntimeError("no reachable backend")

        monkeypatch.setattr(jax, "process_index", boom)
        out = _format()   # rank info degrades, the message survives
        assert out.endswith("|hello")
        assert "host" not in out


class TestLoggerApi:
    def test_get_logger_child_namespacing(self):
        root = alog.get_logger()
        child = alog.get_logger("amp")
        assert root.name == "apex_tpu"
        assert child.name == "apex_tpu.amp"
        assert child.parent is root
        # same name -> same logger object (logging module registry)
        assert alog.get_logger("amp") is child
        assert alog.get_logger() is root

    def test_root_has_single_stream_handler(self):
        root = alog.get_logger()
        assert len(root.handlers) == 1
        assert isinstance(root.handlers[0].formatter,
                          alog.RankInfoFormatter)
        assert root.propagate is False

    def test_set_logging_level_round_trip(self):
        root = alog.get_logger()
        old = root.level
        try:
            alog.set_logging_level(pylogging.DEBUG)
            assert root.level == pylogging.DEBUG
            assert alog.get_logger("child").getEffectiveLevel() == \
                pylogging.DEBUG
            alog.set_logging_level(old)
            assert root.level == old
        finally:
            root.setLevel(old)


class TestPrintRank0:
    def test_prints_on_rank_0(self, capsys):
        alog.print_rank_0("visible")
        assert "visible" in capsys.readouterr().out

    def test_degrades_without_backend(self, monkeypatch, capsys):
        """ISSUE 1 satellite: jax.process_index raising (an
        uninitialized backend) must fall back to printing, the same
        guard RankInfoFormatter.format already applies."""

        def boom():
            raise RuntimeError("backend unreachable")

        monkeypatch.setattr(jax, "process_index", boom)
        alog.print_rank_0("still prints")
        assert "still prints" in capsys.readouterr().out

    def test_silent_on_nonzero_rank(self, monkeypatch, capsys):
        monkeypatch.setattr(jax, "process_index", lambda: 1)
        alog.print_rank_0("suppressed")
        assert capsys.readouterr().out == ""


def test_build_root_logger_idempotent():
    # re-running the builder (e.g. on module reimport) must not stack a
    # second handler onto the shared logging-module registry entry
    fresh = alog._build_root_logger()
    assert fresh is alog.get_logger()
    assert len(fresh.handlers) == 1
