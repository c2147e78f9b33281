"""The benchmark's tokenizer: total and invertible.

Bytes in, one character per id out.  A random-weight model emits ids from
the whole vocabulary; the program's byte tokenizer decodes almost none of
them, so map summaries came out empty and the reduce tree carried no
tokens (PERF.md, PR 22).  Here every id decodes to exactly one character,
``chr(ID_BASE + id)``, and that character encodes back to the same id: the
text a request returns spells the tokens it was served, and a summary fed
to the reduce stage costs one token per token generated.
"""

from __future__ import annotations

ID_BASE = 0x10000  # supplementary planes: no surrogates, 4 UTF-8 bytes a char


class IdTokenizer:
    pad_id = 0
    bos_id = 1
    eos_id = 2

    def __init__(self, vocab_size: int):
        self.vocab_size = int(vocab_size)

    def encode(self, text: str) -> list[int]:
        out: list[int] = []
        for ch in text:
            o = ord(ch) - ID_BASE
            if 0 <= o < self.vocab_size:
                out.append(o)
            else:
                out.extend(b + 3 for b in ch.encode("utf-8"))
        return out

    def decode(self, ids) -> str:
        return "".join(chr(ID_BASE + int(i)) for i in ids)

    def count(self, text: str) -> int:
        return len(self.encode(text))


def spell(ids) -> str:
    """Text whose encoding is exactly ``ids``."""
    return "".join(chr(ID_BASE + int(i)) for i in ids)


def unspell(text: str) -> list[int]:
    """Ids of a served text (every character is one id-character)."""
    return [ord(ch) - ID_BASE for ch in text]


def encode_prompt(tok: IdTokenizer, system_prompt, prompt: str,
                  window: int, max_new: int) -> list[int]:
    """The ids the engine is given for a request: BOS, system prompt and
    prompt joined by a blank line, middle-truncated to ``window - max_new``.
    The benchmark's own statement of the serving contract (README: prompts
    longer than the window keep head and tail); the run compares its length
    with the ``prompt_tokens`` each result reports."""
    text = (system_prompt + "\n\n" if system_prompt else "") + prompt
    ids = [tok.bos_id] + tok.encode(text)
    limit = window - max_new
    if len(ids) > limit:
        head, tail = limit // 2, limit - limit // 2
        ids = ids[:head] + ids[-tail:]
    return ids
