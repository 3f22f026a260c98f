"""The control (the reference one precision step down: bfloat16 counts and
probabilities) has to come out as not correct, and a sound run as
correct, at a size a test run can hold."""

import drive


def test_control_fails_and_program_passes(capsys):
    res = drive.drive("rec-1m.steady", 2 ** 31 + 5, extra=["--control"])
    assert res["correct"] is True
    err = capsys.readouterr().err
    control = {ln.split()[1]: float(ln.split()[2]) for ln in err.splitlines()
               if ln.startswith("control ")}
    limits = {k: v["limit"] for k, v in res["checks"].items()}
    assert any(control[k] > limits[k] for k in limits), control
    assert control["prob_ulp"] > limits["prob_ulp"]
