"""Froid-compiled request admission: the rules compiled agree with the
interpreter."""
import numpy as np
import pytest

from repro.serve.admission import AdmissionPolicy


def test_admission_policy_froid_matches_interpreter():
    reqs = {
        "tier": np.array([0, 1, 2, 0, 2]),
        "prompt_len": np.array([100, 3000, 9000, 40000, 100]),
        "max_new_tokens": np.array([50, 2000, 8000, 10, 100]),
        "temperature": np.array([0.5, 1.5, -1.0, 0.7, 3.0], np.float32),
    }
    on = AdmissionPolicy(froid=True).evaluate(reqs)
    off = AdmissionPolicy(froid=False).evaluate(reqs)
    np.testing.assert_array_equal(on["admit"], off["admit"])
    np.testing.assert_array_equal(on["granted"], off["granted"])
    np.testing.assert_allclose(on["temp"], off["temp"], rtol=1e-6)
    # semantic spot checks
    assert not on["admit"][3]  # prompt > 32768 rejected
    assert on["granted"][0] == 50  # request below cap honored
    assert on["granted"][1] == 512  # tier-1 cap, halved for >2048 prompt
    assert on["temp"][4] == pytest.approx(0.7)  # out-of-range -> default
    assert on["temp"][0] == pytest.approx(0.5)
