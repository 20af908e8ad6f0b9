"""The operation counts against hand counts."""
import json
import os

from benchmark import flops

HERE = os.path.dirname(os.path.abspath(__file__))
CFG = os.path.join(os.path.dirname(HERE), "configs")


def test_bert_base_step_is_bench_py_hand_count():
    """bench.py:_bench_bert, B=32 S=512 M=80, worked by hand:
    dense 12*(4*768^2 + 2*768*3072) = 84,934,656 parameters;
    per token 6*dense + 12*12*768*512 = 566,231,040;
    heads a row 6*(768^2 + 768*30522)*80 + 6*(768^2 + 2*768) = 11,538,293,760;
    step 566,231,040*16,384 + 11,538,293,760*32."""
    cfg = json.load(open(os.path.join(CFG, "bert_base_mlm.json")))
    assert flops.bert_train_step_flops(cfg, 32, 512, 80) == \
        566231040 * 16384 + 11538293760 * 32 == 9646354759680


def test_flash_attention_cost_at_bert_base():
    c = flops.flash_attention_cost(32, 12, 512, 64, 2)
    assert c["fwd"][0] == 4 * 32 * 12 * 512 * 512 * 64   # as the kernel's
    assert c["bwd"][0] == 2 * c["fwd"][0]                 # own cost_estimate
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flops.roofline_seconds(*c["fwd"], peak)
    assert bound == "compute" and abs(t - 25769803776 / 197e12) < 1e-12
    t, bound = flops.roofline_seconds(1e6, 819e9, peak)
    assert bound == "memory" and t == 1.0


def test_decoder_request_flops_by_hand():
    cfg = {"n_embd": 4, "n_layer": 2, "vocab_size": 10, "mlp_ratio": 4}
    # prompt 3 + 2 new: 4 positions run; dense 2*(4*16 + 2*4*16) = 384
    # parameters; contexts 1+2+3+4 = 10; unembedding twice
    assert flops.decoder_request_flops(cfg, 3, 2) == \
        2 * 384 * 4 + 4 * 4 * 2 * 10 + 2 * 4 * 10 * 2
