"""Offline-eval CLI end-to-end: raw text -> tokenized windows -> PPL, and
jsonl -> LAMBADA cloze accuracy, through tools/eval.py with a real vocab
and a warm-started (converted) backbone config surface."""

import json
import subprocess
import sys

import pytest

REPO = __file__.rsplit("/tests/", 1)[0]


@pytest.fixture(scope="module")
def byte_vocab(tmp_path_factory):
    from fleetx_tpu.data.tokenizers.gpt_tokenizer import _bytes_to_unicode

    d = tmp_path_factory.mktemp("vocab")
    be = _bytes_to_unicode()
    vocab = {ch: i for i, ch in enumerate(be.values())}
    vocab["<|endoftext|>"] = len(vocab)
    (d / "vocab.json").write_text(json.dumps(vocab))
    (d / "merges.txt").write_text("#version: tiny\n")
    return str(d)


def _eval_cfg(tmp_path, eval_path, cloze, vocab_dir):
    text = f"""
Global:
  seed: 0
  local_batch_size: 2
  micro_batch_size: 2
Engine:
  max_steps: 1
  save_load:
    save_steps: 1000
    output_dir: {tmp_path}/out
Model:
  module: GPTEvalModule
  vocab_size: 512
  hidden_size: 32
  num_layers: 2
  num_attention_heads: 2
  ffn_hidden_size: 64
  max_position_embeddings: 64
  hidden_dropout_prob: 0.0
  attention_probs_dropout_prob: 0.0
  use_flash_attention: False
Optimizer:
  name: AdamW
  lr:
    name: CosineAnnealingWithWarmupDecay
    decay_steps: 10
    max_lr: 1.0e-3
    min_lr: 1.0e-4
Offline_Eval:
  eval_path: {eval_path}
  vocab_dir: {vocab_dir}
  cloze_eval: {cloze}
  overlapping_eval: 16
  batch_size: 2
  max_seq_len: 64
"""
    p = tmp_path / "eval.yaml"
    p.write_text(text)
    return str(p)


def test_wikitext_ppl_cli(tmp_path, byte_vocab):
    corpus = tmp_path / "wiki.txt"
    corpus.write_text("the quick brown fox jumps over the lazy dog. " * 60)
    cfg = _eval_cfg(tmp_path, str(corpus), "False", byte_vocab)
    r = subprocess.run(
        [sys.executable, f"{REPO}/tools/eval.py", "-c", cfg],
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "FLEETX_LOG_LEVEL": "INFO", "HOME": "/root"},
    )
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    assert "ppl" in r.stdout.lower()


def test_int8_weight_ppl_within_budget(tmp_path, byte_vocab):
    """The quality half of the quantized-serving acceptance gate
    (docs/QUANTIZATION.md): weight-only int8 PTQ through
    ``Offline_Eval.weight_dtype`` must move WikiText perplexity by less
    than the documented 2% relative budget — and must actually move it
    (a zero delta would mean the quantization never engaged)."""
    corpus = tmp_path / "wiki.txt"
    corpus.write_text("the quick brown fox jumps over the lazy dog. " * 60)
    cfg_path = _eval_cfg(tmp_path, str(corpus), "False", byte_vocab)

    sys.path.insert(0, REPO)
    import tools.eval as ev
    from fleetx_tpu.utils.config import get_config

    fp = ev.offline_eval(get_config(cfg_path, show=False))
    qcfg = get_config(cfg_path, show=False)
    qcfg.Offline_Eval.weight_dtype = "int8"
    q8 = ev.offline_eval(qcfg)
    assert q8["tokens"] == fp["tokens"]
    rel = abs(q8["ppl"] - fp["ppl"]) / fp["ppl"]
    assert 0 < rel < 0.02, (fp["ppl"], q8["ppl"], rel)


def test_lambada_cloze_cli(tmp_path, byte_vocab):
    data = tmp_path / "lambada.jsonl"
    data.write_text(
        "\n".join(
            json.dumps({"text": f"sentence number {i} ends with word"})
            for i in range(4)
        )
    )
    cfg = _eval_cfg(tmp_path, str(data), "True", byte_vocab)
    r = subprocess.run(
        [sys.executable, f"{REPO}/tools/eval.py", "-c", cfg],
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "FLEETX_LOG_LEVEL": "INFO", "HOME": "/root"},
    )
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    assert "acc" in r.stdout.lower()
