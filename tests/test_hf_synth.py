"""The HF-checkpoint serving path, covered IN the suite.

tests/test_real_checkpoint.py is opt-in (needs TUNNEL_HF_CKPT); this test
makes the formats path permanent regression coverage by generating the
real-format synthetic export (scripts/make_synth_hf_ckpt.py: genuine
safetensors/tokenizer.json/chat-template files, random weights) into a
tmp dir and running the e2e against it in a subprocess — a fresh
interpreter so the opt-in module's import-time skip gate re-evaluates
with the env set, exactly as a user would run it.

Covers end to end: config.json → ModelConfig, safetensors → convert_hf
transposition (non-square q/o projections crash on layout mistakes),
AutoTokenizer offline load, apply_chat_template expansion, int8 load
quantization, serve → tunnel → /v1/chat/completions.
"""

import importlib.util
import os
import subprocess
import sys

import pytest

# The generator + e2e need the HF tooling stack; skip (not fail) where a
# minimal install lacks it — these are not declared project deps.  Asked
# without importing them: a run that deselects this all-`slow` file still
# collects it, and the three imports were 12 s of every worker's collection.
_MISSING = [m for m in ("tokenizers", "safetensors", "transformers")
            if importlib.util.find_spec(m) is None]

pytestmark = [
    pytest.mark.slow,
    pytest.mark.skipif(bool(_MISSING), reason=f"not installed: {_MISSING}"),
]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_synth_hf_checkpoint_serves_end_to_end(tmp_path):
    ckpt = str(tmp_path / "synth-llama")
    subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "make_synth_hf_ckpt.py"),
         ckpt],
        check=True, timeout=120,
    )
    for fn in ("config.json", "model.safetensors", "tokenizer.json",
               "tokenizer_config.json"):
        assert os.path.exists(os.path.join(ckpt, fn)), fn

    env = dict(
        os.environ,
        TUNNEL_HF_CKPT=ckpt,
        TUNNEL_HF_FAMILY="llama",
        TUNNEL_HF_SYNTH="1",
    )
    proc = subprocess.run(
        [sys.executable, "-m", "pytest",
         os.path.join(REPO, "tests", "test_real_checkpoint.py"), "-q"],
        env=env, timeout=600, capture_output=True,
    )
    assert proc.returncode == 0, (
        f"synthetic-checkpoint e2e failed:\n"
        f"{proc.stdout.decode()[-2000:]}\n{proc.stderr.decode()[-1000:]}"
    )
