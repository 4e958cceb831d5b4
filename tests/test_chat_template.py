"""Chat-template serving (VERDICT r4 item 5).

When the configured tokenizer carries a real chat template, the chat
routes must render prompts through it — the exact formatting the model
was instruction-tuned on — and fall back to the generic role-prefixed
flattening otherwise.  A real `transformers` fast tokenizer is BUILT
locally (no network): a WordLevel vocab + a jinja chat template, saved
to disk and loaded through the same HFTokenizer path a real checkpoint
uses; `apply_chat_template` renders it as transformers' template engine
does (tests/test_tokenizer_loaders.py holds the two to each other).

Capability parity: the reference serves real Ollama models transparently
(tunnel/src/serve.rs:219) and Ollama applies the model's Modelfile
template server-side; engine mode does the same via the HF template.
"""

from __future__ import annotations

from types import SimpleNamespace

import pytest

from p2p_llm_tunnel_tpu.engine.api import EngineAPI, render_chat_prompt

MESSAGES = [
    {"role": "system", "content": "be brief"},
    {"role": "user", "content": "hi there"},
]

TEMPLATE = (
    "{% for m in messages %}<|{{ m['role'] }}|>{{ m['content'] }}"
    "{% endfor %}{% if add_generation_prompt %}<|assistant|>{% endif %}"
)


@pytest.fixture(scope="module")
def hf_dir(tmp_path_factory):
    """A real saved HF fast tokenizer with a chat template, built offline."""
    from tokenizers import Tokenizer, models, pre_tokenizers
    from transformers import PreTrainedTokenizerFast

    words = (
        "be brief hi there <|system|> <|user|> <|assistant|> <unk> <s> </s>"
    ).split()
    vocab = {w: i for i, w in enumerate(words)}
    tok = Tokenizer(models.WordLevel(vocab, unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    fast = PreTrainedTokenizerFast(
        tokenizer_object=tok, unk_token="<unk>", bos_token="<s>",
        eos_token="</s>",
    )
    fast.chat_template = TEMPLATE
    d = tmp_path_factory.mktemp("hf_tok") / "chatmodel"
    fast.save_pretrained(str(d))
    return str(d)


def _bind(engine):
    api = EngineAPI.__new__(EngineAPI)
    api.engine = engine
    api.model_name = "test"
    return api


def test_hf_tokenizer_applies_template(hf_dir):
    from p2p_llm_tunnel_tpu.engine.tokenizer import HFTokenizer

    tok = HFTokenizer(hf_dir)
    # what save_pretrained writes is loaded without transformers (ISSUE 41):
    # tok._t is the package's own reading of the directory
    assert tok.loader == "tokenizers"
    ids = tok.apply_chat_template(MESSAGES)
    assert ids is not None
    # The template's own rendering, tokenized by the same tokenizer: role
    # markers present, generation prompt appended.
    rendered = tok._t.apply_chat_template(MESSAGES, tokenize=False,
                                          add_generation_prompt=True)
    assert rendered == "<|system|>be brief<|user|>hi there<|assistant|>"
    assert ids == tok._t.encode(rendered, add_special_tokens=False)

    api = _bind(SimpleNamespace(tokenizer=tok))
    assert api._chat_prompt_ids(MESSAGES) == ids


def test_templateless_hf_tokenizer_falls_back(hf_dir):
    from p2p_llm_tunnel_tpu.engine.tokenizer import HFTokenizer

    tok = HFTokenizer(hf_dir)
    tok._t.chat_template = None
    assert tok.apply_chat_template(MESSAGES) is None
    api = _bind(SimpleNamespace(tokenizer=tok))
    assert api._chat_prompt_ids(MESSAGES) == tok.encode(
        render_chat_prompt(MESSAGES)
    )


def test_byte_tokenizer_uses_generic_flattening():
    from p2p_llm_tunnel_tpu.engine.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    api = _bind(SimpleNamespace(tokenizer=tok))
    assert api._chat_prompt_ids(MESSAGES) == tok.encode(
        render_chat_prompt(MESSAGES)
    )
    assert render_chat_prompt(MESSAGES) == (
        "system: be brief\nuser: hi there\nassistant:"
    )


def test_assistant_turns_render_as_byte_exact_continuations():
    """ISSUE 14: a resent conversation re-renders to a BYTE-EXACT
    extension of the previous turn's prompt + response stream — the
    assistant cue takes NO space before the content, because generation
    continued the bare cue directly.  This is what lets the conversation
    cache match a returning user's history page-for-page."""
    turn1 = [{"role": "user", "content": "hi"}]
    p1 = render_chat_prompt(turn1)
    resp = "xyz"  # whatever the model streamed after the cue
    turn2 = turn1 + [{"role": "assistant", "content": resp},
                     {"role": "user", "content": "more"}]
    p2 = render_chat_prompt(turn2)
    assert p2.startswith(p1 + resp)
