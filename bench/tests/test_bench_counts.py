"""The benchmark's counts against the program's analytic count and
against sums worked by hand."""
import jax
import pytest

import tiny  # puts bench/ on the path
import counts
import model


@pytest.mark.parametrize("name,total,kv", [
    # 24 x (2*2048*2048 + 2*2048*1024 + 3*2048*8192 + 2*2048)
    #   + 2*92544*2048 + 2048
    ("internlm2_1_8b", 1_889_110_016, 24 * 2 * 8 * 128 * 2),
    # 7 x (2*4096*4096 + 2*4096*256 + 3*4096*13696 + 2*4096)
    #   + 2*65024*4096 + 4096
    ("chatglm3_6b", 1_960_374_272, 7 * 2 * 2 * 128 * 2),
])
def test_counts_match_hand_sums_and_the_program(name, total, kv):
    from repro.launch.roofline import param_count
    conf = model.load_config(name)
    d = model.dims(conf)
    pc = counts.param_count(d)
    assert pc["total"] == total
    # the program's analytic count leaves out the norm gains
    norms = d["n_layers"] * 2 * d["d_model"] + d["d_model"]
    assert param_count(model.program_config(conf))["total"] + norms == total
    assert counts.kv_bytes_per_position(d) == kv
    assert counts.flops_per_token(d) == 2 * (total - pc["embed"] - norms)
    assert counts.decode_step_bytes(d, 1000) == \
        2 * (total - pc["embed"]) + 1000 * kv


def test_weights_have_the_programs_tree():
    conf = tiny.conf()
    w = model.make_weights(model.dims(conf), 2 ** 33 + 1)
    model.check_tree(w, model.program_config(conf))
    assert sum(x.size for x in jax.tree_util.tree_leaves(w)) \
        == counts.param_count(model.dims(conf))["total"]


def test_a_config_that_differs_from_the_program_is_refused():
    conf = tiny.conf()
    conf["config"]["num_key_value_heads"] = 4
    with pytest.raises(ValueError, match="n_kv_heads"):
        model.program_config(conf)
