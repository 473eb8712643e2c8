"""The command needs TPUs: on the CPU it prints no result and exits with
another code than 0. There is no CPU fallback."""


from perfbench import run


def test_refuses_without_tpu(capsys):
    rc = run.main(["--workload", "terasort_100b_1chip", "--seed",
                   str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""
    assert "needs a TPU" in err


def test_unknown_workload_is_an_error(capsys):
    import pytest

    with pytest.raises(KeyError):
        run.main(["--workload", "nope", "--seed", "1", "--seconds", "1"])
    assert capsys.readouterr().out == ""
