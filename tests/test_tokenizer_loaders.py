"""HFTokenizer's two loaders hold each other (ISSUE 41).

A checkpoint directory whose ``tokenizer.json`` says all there is to say is
loaded with the ``tokenizers`` library alone (``loader == "tokenizers"``:
the ``transformers`` import, 18-25 s and ``torch`` with it, stays out of a
serve process's start); everything else keeps ``AutoTokenizer``.  Here every
directory is built offline in the layout of a published checkpoint's files,
and the plain loader's ids, text, sizes and chat renderings are held to
``AutoTokenizer``'s own on the same directory.
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys
from types import SimpleNamespace

import pytest

from p2p_llm_tunnel_tpu.engine.api import EngineAPI
from p2p_llm_tunnel_tpu.engine.tokenizer import HFTokenizer, StreamDecoder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CORPUS = [
    "the quick brown fox jumps over the lazy dog",
    "I do n't think it 's here , is it ? No !",
    "we 've seen they 're fine . I 'm sure",
    "def f(x):\n    return x + 1  # comment",
    "naïve café — 東京 ok",
    "w1 w2 w3 w17 w511 w12",
]
TEXTS = CORPUS + [
    "",
    " leading and trailing ",
    "two  spaces\tand a tab",
    "emoji \U0001f600 outside any vocabulary",
    "<s>marked</s> text <unk>",
    "<|im_start|>user\nhi<|im_end|>\n<|endoftext|>",
    "it ' s a test , really .",
]

CHATML = (
    "{% for message in messages %}{{ '<|im_start|>' + message['role'] + '\n' "
    "+ message['content'] + '<|im_end|>' + '\n' }}{% endfor %}"
    "{% if add_generation_prompt %}{{ '<|im_start|>assistant\n' }}{% endif %}"
)
INST = (
    "{{ bos_token }}{% for message in messages %}"
    "{% if (message['role'] == 'user') != (loop.index0 % 2 == 0) %}"
    "{{ raise_exception('Conversation roles must alternate "
    "user/assistant/user/assistant/...') }}{% endif %}"
    "{% if message['role'] == 'user' %}"
    "{{ '[INST] ' + message['content'] + ' [/INST]' }}"
    "{% elif message['role'] == 'assistant' %}"
    "{{ message['content'] + eos_token }}"
    "{% else %}{{ raise_exception('Only user and assistant roles are "
    "supported!') }}{% endif %}{% endfor %}"
)
NAMED = [
    {"name": "default", "template": CHATML},
    {"name": "tool_use", "template": "{{ raise_exception('not this one') }}"},
]
# what transformers' environment has and jinja's default lacks: the
# generation tag, loop controls, tojson without HTML escapes, trimmed blocks
RICH = (
    "{% for message in messages %}\n"
    "    {% if message['role'] == 'system' %}{% continue %}{% endif %}\n"
    "{{ message | tojson }}{% generation %}<gen>{% endgeneration %}\n"
    "{% endfor %}\n"
    "{{ additional_special_tokens | join(',') }}{{ strftime_now('%Y') }}"
)
TEMPLATES = {"chatml": CHATML, "inst": INST, "named": NAMED, "rich": RICH,
             "none": None}

CHAT = [
    {"role": "user", "content": "hi <there> & 'you'"},
    {"role": "assistant", "content": "the quick brown fox"},
    {"role": "user", "content": "over the lazy dog"},
]
BAD_ORDER = [{"role": "assistant", "content": "the fox"}]


def _special(text):
    return {"content": text, "lstrip": False, "normalized": False,
            "rstrip": False, "single_word": False, "special": True}


def _write(path, tok, config):
    os.makedirs(path, exist_ok=True)
    tok.save(os.path.join(path, "tokenizer.json"))
    with open(os.path.join(path, "tokenizer_config.json"), "w") as f:
        json.dump(config, f)
    return path


def _wordlevel():
    """The file benchmarks/stack.py ``write_tokenizer`` hands every cell."""
    from tokenizers import Tokenizer, models, pre_tokenizers

    words = {f"w{i}": i for i in range(512)}
    tok = Tokenizer(models.WordLevel(words, unk_token="w0"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    return tok, {"tokenizer_class": "PreTrainedTokenizerFast"}


def _trained_bpe(pre_tokenizer=None, normalizer=None):
    from tokenizers import Tokenizer, models, trainers

    tok = Tokenizer(models.BPE())
    if pre_tokenizer is not None:
        tok.pre_tokenizer = pre_tokenizer
    if normalizer is not None:
        tok.normalizer = normalizer
    tok.train_from_iterator(
        CORPUS * 4, trainers.BpeTrainer(vocab_size=420, show_progress=False)
    )
    return tok


def _bytebpe():
    """Byte-level BPE with added special tokens, as Qwen2-7B publishes it."""
    from tokenizers import decoders, pre_tokenizers

    tok = _trained_bpe(pre_tokenizers.ByteLevel(add_prefix_space=False))
    tok.decoder = decoders.ByteLevel()
    names = ["<|endoftext|>", "<|im_start|>", "<|im_end|>"]
    tok.add_special_tokens(names)
    config = {
        "add_prefix_space": False,
        "added_tokens_decoder": {
            str(tok.token_to_id(n)): _special(n) for n in names
        },
        "additional_special_tokens": ["<|im_start|>", "<|im_end|>"],
        "bos_token": None,
        "eos_token": "<|endoftext|>",
        "errors": "replace",
        "model_max_length": 32768,
        "pad_token": "<|endoftext|>",
        "split_special_tokens": False,
        "tokenizer_class": "Qwen2Tokenizer",
        "unk_token": None,
    }
    return tok, config


def _bytefallback():
    """Prepend + replace, byte fallback, no pre-tokenizer: the file
    Mistral-7B-v0.1 publishes (converted from sentencepiece)."""
    from tokenizers import Tokenizer, decoders, models, normalizers

    norm = normalizers.Sequence(
        [normalizers.Prepend("▁"), normalizers.Replace(" ", "▁")]
    )
    model = json.loads(_trained_bpe(normalizer=norm).to_str())["model"]
    head = ["<unk>", "<s>", "</s>"] + [f"<0x{b:02X}>" for b in range(256)]
    pieces = sorted(model["vocab"], key=model["vocab"].get)
    vocab = {p: i for i, p in enumerate(head + pieces)}
    merges = [tuple(m.split(" ")) if isinstance(m, str) else tuple(m)
              for m in model["merges"]]
    tok = Tokenizer(models.BPE(vocab, merges, unk_token="<unk>",
                               fuse_unk=True, byte_fallback=True))
    tok.normalizer = norm
    tok.decoder = decoders.Sequence([
        decoders.Replace("▁", " "), decoders.ByteFallback(), decoders.Fuse(),
        decoders.Strip(" ", 1, 0),
    ])
    tok.add_special_tokens(head[:3])
    config = {
        "add_bos_token": True,
        "add_eos_token": False,
        "added_tokens_decoder": {str(i): _special(n)
                                 for i, n in enumerate(head[:3])},
        "additional_special_tokens": [],
        "bos_token": "<s>",
        "eos_token": "</s>",
        "legacy": True,
        "model_max_length": 1000000000000000019884624838656,
        "pad_token": None,
        "sp_model_kwargs": {},
        "spaces_between_special_tokens": False,
        "tokenizer_class": "LlamaTokenizer",
        "unk_token": "<unk>",
        "use_default_system_prompt": False,
    }
    return tok, config


KINDS = {"wordlevel": _wordlevel, "bytebpe": _bytebpe,
         "bytefallback": _bytefallback}
CLEAN_UP = {"cleanup": True, "raw": False, "unstated": None}


@pytest.fixture(scope="module")
def pair_of(tmp_path_factory):
    """``pair_of(kind, clean_up, template)`` -> (HFTokenizer, AutoTokenizer)
    on one directory, built and loaded once a combination."""
    from transformers import AutoTokenizer

    root = tmp_path_factory.mktemp("tokenizers")
    made = {}

    def get(kind, clean_up="unstated", template="none"):
        key = (kind, clean_up, template)
        if key not in made:
            tok, config = KINDS[kind]()
            if CLEAN_UP[clean_up] is not None:
                config["clean_up_tokenization_spaces"] = CLEAN_UP[clean_up]
            if TEMPLATES[template] is not None:
                config["chat_template"] = TEMPLATES[template]
            path = _write(str(root / "-".join(key)), tok, config)
            made[key] = (HFTokenizer(path), AutoTokenizer.from_pretrained(path))
        return made[key]

    return get


def _ids_to_decode(ours, theirs):
    """What a model may emit: the texts' own ids, then every part of the
    vocabulary in a fixed shuffle, special entries among them."""
    rng = random.Random(41)
    ids = [theirs.encode(t, add_special_tokens=False) for t in TEXTS]
    everything = list(range(len(theirs)))
    rng.shuffle(everything)
    ids += [everything[i:i + 24] for i in range(0, len(everything), 24)]
    return ids


def check_encode(ours, theirs):
    for text in TEXTS:
        assert ours.encode(text) == theirs.encode(
            text, add_special_tokens=False), text


def check_decode(ours, theirs):
    for ids in _ids_to_decode(ours, theirs):
        assert ours.decode(ids) == theirs.decode(
            ids, skip_special_tokens=True), ids


def check_decode_token(ours, theirs):
    """Token by token through StreamDecoder, as the engine streams."""
    wrapped = SimpleNamespace(
        decode=lambda ids: theirs.decode(ids, skip_special_tokens=True))
    for ids in _ids_to_decode(ours, theirs):
        a, b = StreamDecoder(ours), StreamDecoder(wrapped)
        assert [a.push(i) for i in ids] == [b.push(i) for i in ids], ids
        for i in ids[:8]:
            assert ours.decode_token(i) == theirs.decode(
                [i], skip_special_tokens=True)


def check_vocab_size(ours, theirs):
    assert ours.vocab_size == len(theirs)


def check_bos_eos(ours, theirs):
    assert ours.bos_id == (theirs.bos_token_id or 0)
    assert ours.eos_id == (theirs.eos_token_id or 0)


CHECKS = {"encode": check_encode, "decode": check_decode,
          "decode_token": check_decode_token, "vocab_size": check_vocab_size,
          "bos_eos": check_bos_eos}


@pytest.mark.parametrize("check", CHECKS)
@pytest.mark.parametrize("clean_up", CLEAN_UP)
@pytest.mark.parametrize("kind", KINDS)
def test_plain_loader_is_autotokenizer(pair_of, kind, clean_up, check):
    ours, theirs = pair_of(kind, clean_up)
    assert ours.loader == "tokenizers" and ours.fallback_reason is None
    assert type(ours._t).__module__.startswith("p2p_llm_tunnel_tpu")
    CHECKS[check](ours, theirs)


def _bind(tokenizer):
    api = EngineAPI.__new__(EngineAPI)
    api.engine = SimpleNamespace(tokenizer=tokenizer)
    api.model_name = "test"
    return api


# ([INST] templates print bos_token, and the Qwen2 layout names none)
@pytest.mark.parametrize("kind,template", [
    (k, t) for k in ("bytebpe", "bytefallback") for t in TEMPLATES
    if (k, t) != ("bytebpe", "inst")])
def test_chat_template_renders_to_the_same_ids(pair_of, kind, template):
    ours, theirs = pair_of(kind, template=template)
    assert ours.loader == "tokenizers"
    if template == "none":
        assert ours.apply_chat_template(CHAT) is None
        assert not theirs.chat_template
        return
    text = ours._t.apply_chat_template(
        CHAT, add_generation_prompt=True, tokenize=False)
    assert text == theirs.apply_chat_template(
        CHAT, add_generation_prompt=True, tokenize=False)
    ids = ours.apply_chat_template(CHAT)
    assert ids == theirs.apply_chat_template(
        CHAT, add_generation_prompt=True, tokenize=True)
    assert ids and _bind(ours)._chat_prompt_ids(CHAT) == ids


@pytest.mark.parametrize("loader", ["tokenizers", "transformers"])
def test_rejecting_template_is_a_value_error(pair_of, loader):
    """A template's raise_exception reaches the router as the ValueError it
    maps to a 400, whichever loader rendered it."""
    ours, theirs = pair_of("bytefallback", template="inst")
    tok = ours if loader == "tokenizers" else SimpleNamespace(
        apply_chat_template=lambda m: theirs.apply_chat_template(
            m, add_generation_prompt=True, tokenize=True),
        encode=ours.encode)
    with pytest.raises(ValueError, match="roles must alternate"):
        _bind(tok)._chat_prompt_ids(BAD_ORDER)
    with pytest.raises(ValueError, match="Only user and assistant"):
        _bind(tok)._chat_prompt_ids(
            CHAT[:1] + [{"role": "system", "content": "the fox"}])


def test_template_files_win_over_the_config(tmp_path):
    """chat_template.jinja is the default template, additional_chat_templates/
    the named ones, and together they replace tokenizer_config.json's."""
    from transformers import AutoTokenizer

    tok, config = _bytebpe()
    config["chat_template"] = "{{ raise_exception('the config entry') }}"
    path = _write(str(tmp_path / "files"), tok, config)
    with open(os.path.join(path, "chat_template.jinja"), "w") as f:
        f.write(CHATML)
    ours, theirs = HFTokenizer(path), AutoTokenizer.from_pretrained(path)
    assert ours.loader == "tokenizers"
    assert ours._t.chat_template == theirs.chat_template == CHATML
    os.makedirs(os.path.join(path, "additional_chat_templates"))
    with open(os.path.join(path, "additional_chat_templates", "rag.jinja"),
              "w") as f:
        f.write("{{ raise_exception('not the default') }}")
    ours, theirs = HFTokenizer(path), AutoTokenizer.from_pretrained(path)
    assert ours._t.chat_template == theirs.chat_template
    assert sorted(theirs.chat_template) == ["default", "rag"]
    assert ours.apply_chat_template(CHAT) == theirs.apply_chat_template(
        CHAT, add_generation_prompt=True, tokenize=True)


@pytest.mark.parametrize("check", list(CHECKS) + ["chat"])
def test_the_older_layout_overlays_special_tokens_map(tmp_path, check):
    """No added_tokens_decoder in the config: special_tokens_map.json names
    the special tokens (over the config's), dict entries among them, as
    scripts/make_synth_hf_ckpt.py and older checkpoints write it."""
    from transformers import AutoTokenizer

    tok, config = _bytebpe()
    del config["added_tokens_decoder"], config["additional_special_tokens"]
    config.update(eos_token="<|im_end|>", chat_template=RICH)
    path = _write(str(tmp_path / "older"), tok, config)
    with open(os.path.join(path, "special_tokens_map.json"), "w") as f:
        json.dump({"eos_token": _special("<|endoftext|>"),
                   "bos_token": "<|im_start|>",
                   "additional_special_tokens": ["<|im_end|>"]}, f)
    ours, theirs = HFTokenizer(path), AutoTokenizer.from_pretrained(path)
    assert ours.loader == "tokenizers"
    assert ours.eos_id == tok.token_to_id("<|endoftext|>")
    if check == "chat":
        assert ours.apply_chat_template(CHAT) == theirs.apply_chat_template(
            CHAT, add_generation_prompt=True, tokenize=True)
    else:
        CHECKS[check](ours, theirs)


# -- what the plain loader refuses, and AutoTokenizer then loads ---------

def _no_tokenizer_json(path):
    """vocab.json + merges.txt alone: a slow checkpoint transformers converts."""
    tok, config = _bytebpe()
    model = json.loads(tok.to_str())["model"]
    os.makedirs(path)
    for n in ("<|endoftext|>", "<|im_start|>", "<|im_end|>"):
        model["vocab"][n] = tok.token_to_id(n)
    with open(os.path.join(path, "vocab.json"), "w") as f:
        json.dump(model["vocab"], f)
    with open(os.path.join(path, "merges.txt"), "w") as f:
        f.write("#version: 0.2\n" + "\n".join(
            m if isinstance(m, str) else " ".join(m) for m in model["merges"]))
    with open(os.path.join(path, "tokenizer_config.json"), "w") as f:
        json.dump(config, f)


def _auto_map(path):
    tok, config = _bytebpe()
    config["auto_map"] = {"AutoTokenizer": ["tokenization_x.XTokenizer", None]}
    _write(path, tok, config)


def _added_token_the_file_lacks(path):
    tok, config = _bytebpe()
    config["added_tokens_decoder"][str(tok.get_vocab_size())] = _special(
        "<|extra|>")
    _write(path, tok, config)


def _added_token_with_other_flags(path):
    tok, config = _bytebpe()
    idx = str(tok.token_to_id("<|im_start|>"))
    config["added_tokens_decoder"][idx]["special"] = False
    config["additional_special_tokens"] = []
    _write(path, tok, config)


def _special_token_the_file_has_as_a_word(path):
    tok, config = _wordlevel()
    _write(path, tok, {**config, "eos_token": "w7"})


def _another_tokenizer_class(path):
    tok, config = _bytebpe()
    _write(path, tok, {**config, "tokenizer_class": "GPT2Tokenizer"})


def _prefix_space_the_config_does_not_state(path):
    from tokenizers import decoders, pre_tokenizers

    tok = _trained_bpe(pre_tokenizers.ByteLevel(add_prefix_space=True))
    tok.decoder = decoders.ByteLevel()
    _write(path, tok, {"tokenizer_class": "PreTrainedTokenizerFast"})


def _older_layout_with_added_tokens_json(path):
    tok, config = _bytebpe()
    del config["added_tokens_decoder"]
    _write(path, tok, config)
    with open(os.path.join(path, "added_tokens.json"), "w") as f:
        json.dump({"<|extra|>": tok.get_vocab_size()}, f)


REFUSED = {
    "no_tokenizer_json": (_no_tokenizer_json, "no local tokenizer.json"),
    "auto_map": (_auto_map, "auto_map"),
    "added_token_the_file_lacks": (_added_token_the_file_lacks,
                                   "added_tokens_decoder"),
    "added_token_with_other_flags": (_added_token_with_other_flags,
                                     "added_tokens_decoder"),
    "special_token_the_file_has_as_a_word": (
        _special_token_the_file_has_as_a_word, "special token 'w7'"),
    "another_tokenizer_class": (_another_tokenizer_class, "GPT2Tokenizer"),
    "prefix_space_the_config_does_not_state": (
        _prefix_space_the_config_does_not_state, "add_prefix_space"),
    "older_layout_with_added_tokens_json": (
        _older_layout_with_added_tokens_json, "added_tokens.json"),
}
#: where tokenizer.json read alone would have given other ids than
#: AutoTokenizer does: the fallback is not a formality
DIFFERS = {"added_token_the_file_lacks", "special_token_the_file_has_as_a_word",
           "prefix_space_the_config_does_not_state",
           "older_layout_with_added_tokens_json"}
PROBES = TEXTS + ["a<|extra|>b", "w3 w7 w9"]


@pytest.mark.parametrize("case", REFUSED)
def test_refused_directory_keeps_autotokenizer(tmp_path, case):
    from tokenizers import Tokenizer
    from transformers import AutoTokenizer

    build, why = REFUSED[case]
    path = str(tmp_path / case)
    build(path)
    ours, theirs = HFTokenizer(path), AutoTokenizer.from_pretrained(path)
    assert ours.loader == "transformers"
    assert why in ours.fallback_reason
    CHECKS["encode"](ours, theirs)
    CHECKS["vocab_size"](ours, theirs)
    CHECKS["bos_eos"](ours, theirs)
    probe_ids = [ours.encode(t) for t in PROBES]
    assert probe_ids == [theirs.encode(t, add_special_tokens=False)
                         for t in PROBES]
    assert [ours.decode(i) for i in probe_ids] == [
        theirs.decode(i, skip_special_tokens=True) for i in probe_ids]
    if case in DIFFERS:
        raw = Tokenizer.from_file(os.path.join(path, "tokenizer.json"))
        alone = [raw.encode(t, add_special_tokens=False).ids for t in PROBES]
        assert (alone, [raw.decode(i) for i in alone],
                raw.get_vocab_size()) != (
            probe_ids, [ours.decode(i) for i in probe_ids], ours.vocab_size)


def test_a_hub_name_is_left_to_autotokenizer(tmp_path, monkeypatch):
    from p2p_llm_tunnel_tpu.engine.tokenizer import _PlainTokenizer

    monkeypatch.chdir(tmp_path)
    assert _PlainTokenizer.load("mistralai/Mistral-7B-v0.1") == (
        None, "no local tokenizer.json")


def test_the_plain_loader_imports_neither_transformers_nor_torch(tmp_path):
    """A serve process's start, in a process of its own: the directory a
    benchmark cell gets, then a published layout with a chat template."""
    paths = []
    for kind in ("wordlevel", "bytebpe"):
        tok, config = KINDS[kind]()
        if kind == "bytebpe":
            config["chat_template"] = CHATML
        paths.append(_write(str(tmp_path / kind), tok, config))
    code = (
        "import json, sys\n"
        "from p2p_llm_tunnel_tpu.engine.tokenizer import HFTokenizer\n"
        "out = []\n"
        "for path in sys.argv[1:]:\n"
        "    tok = HFTokenizer(path)\n"
        "    ids = tok.encode('w3 the quick w12 fox')\n"
        "    chat = tok.apply_chat_template([{'role': 'user', 'content': 'hi'}])\n"
        "    out.append({'loader': tok.loader, 'ids': ids, 'chat': chat,\n"
        "                'text': tok.decode(ids), 'entries': tok.vocab_size})\n"
        "print(json.dumps({'out': out, 'loaded': sorted(\n"
        "    m for m in ('transformers', 'torch', 'jinja2') if m in sys.modules)}))\n"
    )
    env = dict(os.environ, PYTHONPATH=REPO)
    run = subprocess.run([sys.executable, "-c", code] + paths, env=env,
                         capture_output=True, text=True, timeout=120)
    assert run.returncode == 0, run.stderr[-2000:]
    said = json.loads(run.stdout.strip().splitlines()[-1])
    assert said["loaded"] == ["jinja2"]
    assert [o["loader"] for o in said["out"]] == ["tokenizers", "tokenizers"]
    for path, got in zip(paths, said["out"]):
        here = HFTokenizer(path)
        assert got["ids"] == here.encode("w3 the quick w12 fox")
        assert got["text"] == here.decode(got["ids"])
        assert got["entries"] == here.vocab_size
        assert got["chat"] == here.apply_chat_template(
            [{"role": "user", "content": "hi"}])
    assert said["out"][0]["ids"][0] == 3 and said["out"][0]["chat"] is None
    assert said["out"][1]["chat"]
