"""Tokenizers: a dependency-free byte-level tokenizer for tests/benches and
an adapter for HuggingFace tokenizers for real checkpoints."""

from __future__ import annotations

import datetime
import glob
import json
import os
from typing import List, Optional, Protocol


class Tokenizer(Protocol):
    bos_id: int
    eos_id: int

    @property
    def vocab_size(self) -> int: ...

    def encode(self, text: str) -> List[int]: ...

    def decode(self, ids: List[int]) -> str: ...

    def decode_token(self, token_id: int) -> str: ...


class ByteTokenizer:
    """UTF-8 bytes + BOS/EOS/PAD. Deterministic, zero deps, vocab 259.

    ``decode_token`` is incremental-safe for ASCII; multi-byte codepoints are
    buffered by StreamDecoder below.

    ``vocab_size`` can be widened (e.g. to a real model's full vocabulary so
    a benchmark exercises the true embed/lm_head shapes); ids >= 256 decode
    to "" and encode never produces them.
    """

    PAD = 256
    BOS = 257
    EOS = 258

    bos_id = BOS
    eos_id = EOS

    def __init__(self, vocab_size: int = 259):
        self._vocab_size = max(int(vocab_size), 259)

    @property
    def vocab_size(self) -> int:
        return self._vocab_size

    def encode(self, text: str) -> List[int]:
        return list(text.encode("utf-8"))

    def decode(self, ids: List[int]) -> str:
        return bytes(i for i in ids if i < 256).decode("utf-8", "replace")

    def decode_token(self, token_id: int) -> str:
        if token_id >= 256:
            return ""
        return bytes([token_id]).decode("utf-8", "replace")


class Latin1Tokenizer(ByteTokenizer):
    """ByteTokenizer with a BIJECTIVE byte<->text mapping (latin-1).

    Random-weight models generate arbitrary bytes, which the UTF-8
    ByteTokenizer cannot round-trip through client-visible text
    (invalid sequences decode to replacement chars).  Latin-1 maps every
    byte to exactly one codepoint, so a chat client that replays an
    assistant message re-encodes to the EXACT bytes sitting in the KV
    cache — the property the conversation-cache replay experiment
    (ISSUE 14, loadgen --turns against testing.local_stack) needs to hit
    finished-stream pages with random weights.  Real checkpoints emit
    valid UTF-8 and don't need this.
    """

    def encode(self, text: str) -> List[int]:
        return list(text.encode("latin-1", "replace"))

    def decode(self, ids: List[int]) -> str:
        return bytes(i for i in ids if i < 256).decode("latin-1")

    def decode_token(self, token_id: int) -> str:
        if token_id >= 256:
            return ""
        return bytes([token_id]).decode("latin-1")


class StreamDecoder:
    """Incremental detokenizer that never emits broken UTF-8 mid-codepoint.

    Only the undecoded tail is kept, so each push costs O(pending tokens)
    (normally 1-4), not O(all tokens so far).
    """

    def __init__(self, tokenizer) -> None:
        self._tok = tokenizer
        self._pending: List[int] = []

    def push(self, token_id: int) -> str:
        """Feed one token id; returns newly-complete text (may be '')."""
        self._pending.append(token_id)
        text = self._tok.decode(self._pending)
        # A trailing replacement char usually means a split codepoint; hold
        # the pending ids until the codepoint completes.
        if text.endswith("�") and len(self._pending) < 8:
            return ""
        self._pending.clear()
        return text


#: Tokenizer classes whose fast form in ``transformers`` adds nothing to what
#: ``tokenizer.json`` says about encoding without special tokens and about
#: decoding, by name without ``Fast``, each with what its ``__init__``
#: defaults where ``tokenizer_config.json`` is silent.  Any other class
#: (BERT's rewrites the normaliser, RoBERTa's the pre-tokenizer, ...) keeps
#: ``AutoTokenizer``.
_PLAIN_CLASSES = {
    "PreTrainedTokenizer": {},
    "LlamaTokenizer": {"unk_token": "<unk>", "bos_token": "<s>",
                       "eos_token": "</s>", "add_prefix_space": None},
    "Qwen2Tokenizer": {"unk_token": "<|endoftext|>",
                       "eos_token": "<|endoftext|>",
                       "pad_token": "<|endoftext|>"},
}
#: ``tokenizer_config.json`` keys that make ``transformers`` build the
#: tokenizer from something else than ``tokenizer.json`` as it lies, or
#: change it after the load.
_REBUILDING_KEYS = ("auto_map", "extra_special_tokens", "fast_tokenizer_files",
                    "from_slow", "fix_mistral_regex", "split_special_tokens")
_SPECIAL_TOKEN_KEYS = ("bos_token", "eos_token", "unk_token", "sep_token",
                       "pad_token", "cls_token", "mask_token")


def _read_json(directory: str, name: str):
    path = os.path.join(directory, name)
    if not os.path.isfile(path):
        return None
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _token_text(token) -> Optional[str]:
    """A special token of the config: a string or an ``AddedToken`` dict."""
    return token["content"] if isinstance(token, dict) else token


def _chat_template_of(directory: str, config: dict):
    """The template(s) ``from_pretrained`` would hand the tokenizer: the
    directory's template files win over the config's entry; a list of
    named templates becomes a dict."""
    paths = {"default": os.path.join(directory, "chat_template.jinja")}
    for path in glob.glob(
        os.path.join(directory, "additional_chat_templates", "*.jinja")
    ):
        paths[os.path.basename(path)[: -len(".jinja")]] = path
    read = {}
    for name, path in paths.items():
        if os.path.isfile(path):
            with open(path, encoding="utf-8") as f:
                read[name] = f.read()
    if read:
        return read["default"] if list(read) == ["default"] else read
    template = config.get("chat_template")
    if isinstance(template, (list, tuple)):
        return {t["name"]: t["template"] for t in template}
    return template


def _template_environment():
    """The jinja2 environment ``transformers`` renders chat templates in
    (utils/chat_template_utils.py ``_compile_jinja_template``)."""
    import jinja2
    from jinja2.ext import Extension, loopcontrols
    from jinja2.sandbox import ImmutableSandboxedEnvironment

    class Generation(Extension):
        """``{% generation %}`` marks the assistant's spans for a training
        mask; rendering passes the body through."""

        tags = {"generation"}

        def parse(self, parser):
            lineno = next(parser.stream).lineno
            body = parser.parse_statements(
                ["name:endgeneration"], drop_needle=True
            )
            return jinja2.nodes.CallBlock(
                self.call_method("_body"), [], [], body
            ).set_lineno(lineno)

        def _body(self, caller):
            return caller()

    def raise_exception(message):
        raise jinja2.exceptions.TemplateError(message)

    def tojson(x, ensure_ascii=False, indent=None, separators=None,
               sort_keys=False):
        # jinja's own filter escapes HTML characters
        return json.dumps(x, ensure_ascii=ensure_ascii, indent=indent,
                          separators=separators, sort_keys=sort_keys)

    def strftime_now(format):
        return datetime.datetime.now().strftime(format)

    env = ImmutableSandboxedEnvironment(
        trim_blocks=True, lstrip_blocks=True,
        extensions=[Generation, loopcontrols],
    )
    env.filters["tojson"] = tojson
    env.globals["raise_exception"] = raise_exception
    env.globals["strftime_now"] = strftime_now
    return env


class _PlainTokenizer:
    """The calls HFTokenizer makes of a ``transformers`` fast tokenizer,
    with the arguments it makes them with, over the ``tokenizers`` library
    and ``jinja2`` alone: importing ``transformers`` (which drags ``torch``
    in) is 18-25 s of a serve process's start, and a fast tokenizer is
    ``tokenizers.Tokenizer.from_file`` plus ``tokenizer_config.json``.
    ``load`` refuses, with the reason, every directory for which that
    cannot be shown to give ``AutoTokenizer``'s ids;
    tests/test_tokenizer_loaders.py holds the two to each other."""

    def __init__(self, tok, config: dict, chat_template):
        self._tok = tok
        # transformers' default since 4.45; the identity test holds it to
        # the installed one
        self._clean_up = bool(
            config.get("clean_up_tokenization_spaces", False))
        self.special_tokens_map = {
            k: _token_text(config[k])
            for k in _SPECIAL_TOKEN_KEYS if config.get(k)
        }
        if config.get("additional_special_tokens"):
            self.special_tokens_map["additional_special_tokens"] = [
                _token_text(t) for t in config["additional_special_tokens"]
            ]
        self.chat_template = chat_template
        # jinja2's import belongs to start-up, not to the first chat request
        self._env = _template_environment() if chat_template else None
        self._compiled = None

    @classmethod
    def load(cls, name_or_path: str):
        """``(tokenizer, None)``, or ``(None, why AutoTokenizer loads it)``."""
        directory = str(name_or_path)
        if not os.path.isfile(os.path.join(directory, "tokenizer.json")):
            return None, "no local tokenizer.json"
        config = _read_json(directory, "tokenizer_config.json") or {}
        name = config.get("tokenizer_class") or ""
        defaults = _PLAIN_CLASSES.get(name.removesuffix("Fast"))
        if defaults is None:
            return None, f"tokenizer_class {name or 'not stated'}"
        for key in _REBUILDING_KEYS:
            if config.get(key):
                return None, f"tokenizer_config.json sets {key}"
        config = {**defaults, **config}
        if "added_tokens_decoder" not in config:
            # the older layout: the special tokens' own file overlays the
            # config, and added_tokens.json adds entries
            if os.path.isfile(os.path.join(directory, "added_tokens.json")):
                return None, "added_tokens.json"
            overlay = _read_json(directory, "special_tokens_map.json") or {}
            extra = config.get("additional_special_tokens") or []
            more = overlay.get("additional_special_tokens") or []
            extra = extra + [t for t in more if t not in extra]
            config.update(overlay, additional_special_tokens=extra)

        from tokenizers import AddedToken, Tokenizer

        tok = Tokenizer.from_file(os.path.join(directory, "tokenizer.json"))
        # transformers adds every token of the config that the file lacks
        # or holds with other flags, and every special token named there,
        # as AddedToken(text, special=True): the file must hold them so
        held = {repr(t) for t in tok.get_added_tokens_decoder().values()}

        def holds(token, **flags) -> bool:
            spec = token if isinstance(token, dict) else {"content": token}
            spec = {k: v for k, v in spec.items() if k != "__type"}
            return repr(AddedToken(**{**spec, **flags})) in held

        for idx, entry in config.get("added_tokens_decoder", {}).items():
            if not holds(entry):
                return None, (f"added_tokens_decoder[{idx}] not in "
                              "tokenizer.json")
        named = [config.get(k) for k in _SPECIAL_TOKEN_KEYS]
        named += config.get("additional_special_tokens") or []
        for token in named:
            if token and not holds(token, special=True):
                return None, (f"special token {_token_text(token)!r} not a "
                              "special entry of tokenizer.json")
        # ... and rebuilds a pre-tokenizer whose add_prefix_space is not
        # the config's (LlamaTokenizerFast: from sentencepiece when stated)
        prefix_space = config.get("add_prefix_space", False)
        state = {}
        if tok.pre_tokenizer is not None:
            state = json.loads(tok.pre_tokenizer.__getstate__())
        from_sentencepiece = ("add_prefix_space" in defaults
                              and prefix_space is not None)
        in_file = state.get("add_prefix_space", prefix_space)
        if from_sentencepiece or in_file != prefix_space:
            return None, "add_prefix_space"
        # encode() there never truncates or pads, whatever the file says
        tok.no_truncation()
        tok.no_padding()
        return cls(tok, config, _chat_template_of(directory, config)), None

    def __len__(self) -> int:
        return self._tok.get_vocab_size(with_added_tokens=True)

    def _id(self, key: str) -> Optional[int]:
        token = self.special_tokens_map.get(key)
        return None if token is None else self._tok.token_to_id(token)

    @property
    def bos_token_id(self) -> Optional[int]:
        return self._id("bos_token")

    @property
    def eos_token_id(self) -> Optional[int]:
        return self._id("eos_token")

    def encode(self, text: str, add_special_tokens: bool = False) -> List[int]:
        return self._tok.encode(
            text, add_special_tokens=add_special_tokens).ids

    def decode(self, ids: List[int], skip_special_tokens: bool = True) -> str:
        text = self._tok.decode(ids, skip_special_tokens=skip_special_tokens)
        if self._clean_up:
            # PreTrainedTokenizerBase.clean_up_tokenization, in its order
            for mark in (".", "?", "!", ","):
                text = text.replace(" " + mark, mark)
            text = text.replace(" ' ", "'")
            for tail in ("n't", "'m", "'s", "'ve", "'re"):
                text = text.replace(" " + tail, tail)
        return text

    def _template(self):
        """The default template, compiled at its first use: a malformed
        one fails the request that asks for it, not the start."""
        if self._compiled is None:
            template = self.chat_template
            if isinstance(template, dict):
                if "default" not in template:
                    raise ValueError(
                        "the checkpoint has several chat templates and no "
                        f"default: {sorted(template)}"
                    )
                template = template["default"]
            self._compiled = self._env.from_string(template)
        return self._compiled

    def apply_chat_template(self, messages,
                            add_generation_prompt: bool = False,
                            tokenize: bool = True):
        rendered = self._template().render(
            messages=messages, tools=None, documents=None,
            add_generation_prompt=add_generation_prompt,
            **self.special_tokens_map,
        )
        return self.encode(rendered) if tokenize else rendered


class HFTokenizer:
    """A checkpoint's own tokenizer: one adapter, two loaders.

    A local directory whose ``tokenizer.json`` says all there is to say
    (``_PlainTokenizer.load``) is loaded without ``transformers``; a hub
    name, a sentencepiece-only checkpoint, a custom class and whatever else
    that loader refuses go through ``AutoTokenizer.from_pretrained`` as
    before (lazy import; CPU-only dep).  ``loader`` names the one that
    engaged, ``fallback_reason`` why the plain one did not."""

    def __init__(self, name_or_path: str):
        self._t, self.fallback_reason = _PlainTokenizer.load(name_or_path)
        self.loader = "tokenizers"
        if self._t is None:
            try:
                from transformers import AutoTokenizer  # lazy: big import
            except ImportError as e:
                raise ImportError(
                    f"{name_or_path} needs transformers "
                    f"({self.fallback_reason}): {e}"
                ) from e
            self._t = AutoTokenizer.from_pretrained(name_or_path)
            self.loader = "transformers"
        self.bos_id = self._t.bos_token_id or 0
        self.eos_id = self._t.eos_token_id or 0

    @property
    def vocab_size(self) -> int:
        return len(self._t)

    def encode(self, text: str) -> List[int]:
        return self._t.encode(text, add_special_tokens=False)

    def decode(self, ids: List[int]) -> str:
        return self._t.decode(ids, skip_special_tokens=True)

    def decode_token(self, token_id: int) -> str:
        return self._t.decode([token_id], skip_special_tokens=True)

    def apply_chat_template(self, messages) -> Optional[List[int]]:
        """Token ids via the checkpoint's OWN chat template (the exact
        rendering the model was instruction-tuned on), or None when the
        tokenizer ships no template — the API layer then falls back to the
        generic render_chat_prompt flattening.

        Capability parity with the reference serving real Ollama models
        transparently (tunnel/src/serve.rs:219): Ollama applies the model's
        Modelfile template server-side; our engine mode does the same via
        the HF tokenizer's template."""
        if not getattr(self._t, "chat_template", None):
            return None
        return self._t.apply_chat_template(
            messages, add_generation_prompt=True, tokenize=True
        )
