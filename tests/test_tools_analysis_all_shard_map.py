"""The shard_map case of the consolidated analyzer pin; the body, and the
gspmd case, are in tests/test_tools_analysis_all.py. A file of its own so
that `--dist loadfile` gives the two halves of the manifest to two
workers."""

import pytest

from test_tools_analysis_all import check_all_tiers


class TestAnalysisAllSmoke:
    @pytest.mark.parametrize("backend", ["shard_map"])
    def test_all_tiers_clean_and_locks_reproducible_within_budget(
            self, backend, tmp_path):
        # 147.3 s alone, 158.4-189.7 s under six workers (210.7 s once
        # under four); the gspmd case's limit
        check_all_tiers(backend, tmp_path, timeout=480)


class TestBackendShareCli:
    def test_a_backend_share_is_only_regenerated(self, capsys):
        """One backend's share of the manifest checked against the whole
        committed manifest would call the other share's rows missing, so
        a share only regenerates (--write-manifest); refused before
        anything is lowered."""
        from dcgan_tpu.analysis import semantic
        from dcgan_tpu.analysis.__main__ import main

        with pytest.raises(ValueError, match="share"):
            semantic.run_semantic(backends=("gspmd",))
        for argv, why in ((["--backend", "gspmd"], "--backend require"),
                          (["--semantic", "--backend", "shard_map"],
                           "only be regenerated")):
            with pytest.raises(SystemExit):
                main(argv)
            assert why in capsys.readouterr().err
