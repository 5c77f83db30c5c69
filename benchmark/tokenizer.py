"""The benchmark's own word-hash tokenizer, handed to the program's models
(``JaxEncoder(tokenizer=...)``, ``JaxDecoderLM.tokenizer``) and to the plain
references alike, so that both sides read the same ids.  ``log`` keeps the
last texts encoded with their ids: that is how ``correct`` learns the exact
prompt ids an answer was decoded from."""

from __future__ import annotations

import collections
import re
import zlib

_WORD = re.compile(r"\w+|[^\w\s]")


class WordHash:
    def __init__(self, vocab_size: int, log: int = 0):
        self.vocab_size = vocab_size
        self.log = collections.deque(maxlen=log) if log else None

    def encode(self, text: str) -> list:
        n = self.vocab_size - 4  # ids 0..3 reserved (pad/unk/cls/sep)
        ids = [4 + zlib.crc32(w.encode()) % n
               for w in _WORD.findall((text or "").lower())]
        if self.log is not None:
            self.log.append((text, ids))
        return ids

    def count_tokens(self, text: str) -> int:
        return len(_WORD.findall(text or ""))

    def decode(self, ids) -> str:
        return " ".join(f"<{int(t)}>" for t in ids)


def parse_answer(text: str) -> list:
    """Token ids back out of ``decode``'s text."""
    return [int(t) for t in re.findall(r"<(\d+)>", text or "")]
