"""``chip_smoke.py`` on the CPU: its one-chip phases pass against the
oracle at a small register file and short streams (the chip runs them at
the default width), and its entry point refuses to run without a TPU."""
import json

import chip_smoke
from repro.core.packets import SwitchConfig


def test_one_chip_phases_pass_at_small_width(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "N_YCSB", 400)
    monkeypatch.setattr(chip_smoke, "N_SMALLBANK", 300)
    dev = chip_smoke.run(chip_smoke.ONE_CHIP,
                         SwitchConfig(regs_per_stage=1024))
    assert dev["platform"] == "cpu"
    phases = [json.loads(line)["phase"]
              for line in capsys.readouterr().out.splitlines()]
    assert phases == [name for name, _ in chip_smoke.ONE_CHIP]


def test_entry_point_fails_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert "no TPU found" in captured.err
    assert '"ok"' not in captured.out
