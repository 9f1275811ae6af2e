"""The control at a size a test run holds: the float32 reference with
float8 (e4m3) weights, one precision step below the configured bf16,
read on the same served tokens, fails each tiny cell's limit and reads
``correct`` false, while the program passes it, on three seeds."""

import pytest

from chipbench import cell as cellmod
from chipbench import serve
from chipbench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("workload", ["tiny-dense.chat", "tiny-ssm.chat"])
@pytest.mark.parametrize("seed", [1, 4, 2**33 + 9])
def test_the_control_fails_and_the_program_passes(root, workload, seed):
    c = cellmod.load(root, workload)
    out = serve.run(c, seed, 1.5, False, 0.0, tiny.PEAKS,
                    log=lambda m: None, control=True)
    limit = c.traffic["correct"]["max_logit_gap"]
    assert out["compare"]["gap"] <= limit < out["compare"]["control_gap"]
    # the control, in the program's place, goes through the same decision
    assert out["checks"]["max_logit_gap"]["value"] == out["compare"]["control_gap"]
    assert out["correct"] is False
