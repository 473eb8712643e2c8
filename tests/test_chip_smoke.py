"""chip_smoke.py has no CPU branch: on the CPU mesh it must refuse,
exit non-zero and print no result line."""

import chip_smoke


def test_refuses_without_a_tpu(capsys):
    rc = chip_smoke.main([])
    out = capsys.readouterr().out
    assert rc != 0
    assert '"ok": true' not in out


def test_four_chip_option_refuses_without_a_tpu(capsys):
    assert chip_smoke.main(["--chips", "4"]) != 0
    assert '"ok"' not in capsys.readouterr().out


def _tiny(monkeypatch):
    monkeypatch.setattr(chip_smoke, "RECORDS", 1 << 12)
    monkeypatch.setattr(chip_smoke, "ROWS", 1 << 10)
    monkeypatch.setattr(chip_smoke, "REPEATS", 2)


def test_one_chip_phases_verify_at_tiny_size(monkeypatch, capsys):
    import jax

    _tiny(monkeypatch)
    clock = chip_smoke.CompileClock()
    try:
        assert chip_smoke.one_chip(jax.devices(), 0, clock)
    finally:
        clock.close()
    out = capsys.readouterr().out
    assert '"sort_mode": "wide"' in out
    assert '"pallas_kernels": "none (transport=xla' in out


def test_four_chip_ring_matches_xla_but_needs_compiled_kernel(
        monkeypatch, capsys):
    import jax

    _tiny(monkeypatch)
    clock = chip_smoke.CompileClock()
    try:
        # the CPU mesh interprets the ring kernel, so the phase refuses
        assert not chip_smoke.four_chips(jax.devices(), 0, clock)
    finally:
        clock.close()
    out = capsys.readouterr().out
    assert "bit-identical=True" in out
    assert "fused ring kernel compiled=False" in out
    assert "fused ring x1: interpreted" in out
