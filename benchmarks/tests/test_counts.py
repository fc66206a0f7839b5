"""The count functions against shapes worked by hand."""

import run as harness


def _config(name):
    return harness.load_json(f"{harness.HERE}/configs/{name}.json")


def test_sage_counts_by_hand():
    counts = harness.load_module("counts", "sage").per_step(_config("sage-products-id"))
    rows = [1024, 15360, 153600, 768000]
    assert counts["sampled_nodes"] == sum(rows) == 937984
    assert counts["sampled_edges"] == 936960
    enc = 2 * 937984 * 100 * 128
    conv = (
        2 * (1024 + 15360 + 153600) * 256 * 256  # layer 0: [x | mean] is 2 x 128 wide
        + 2 * (1024 + 15360) * 512 * 256
        + 2 * 1024 * 512 * 256
    )
    out = 2 * 1024 * 256 * 47
    assert counts["flops"] == 2 * enc + 3 * (conv + out)
    weights = (100 * 128 + 128) + (256 * 256 + 256) + 2 * (512 * 256 + 256) + 256 * 47 + 47
    assert counts["bytes"] == (
        937984 * 100 * 2 + 937984 * 128 * 4 * 8 + weights * 4 * 7 + 936960 * 4
    )


def test_skipgram_counts_by_hand():
    counts = harness.load_module("counts", "skipgram").per_step(_config("deepwalk-products"))
    valid = 2 * sum(41 - off for off in range(1, 11))
    assert valid == 710
    assert counts["examples"] == 256 * 710 == 181760
    assert counts["table_rows"] == 181760 * 7
    assert counts["flops"] == 3 * 2 * 181760 * 6 * 128
    assert counts["bytes"] == 181760 * 7 * 128 * 4 * 8 + 256 * 40 * 4


def test_examples_per_step_matches_the_program_mask():
    fam = harness.load_module("families", "skipgram")
    assert fam.valid_pairs_per_walk(40, 10) == 710
    ref = harness.load_module("reference", "skipgram")
    _, _, valid = ref.pair_columns(40, 10)
    assert int(valid.sum()) == 710 and len(valid) == 820
