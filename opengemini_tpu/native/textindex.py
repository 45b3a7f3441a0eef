"""Full-text index binding (native/textindex.cpp) with python fallback.

Reference: engine/index/textindex (C++ via cgo: AddDocument,
RetrievePostingList) powering log-search. Query integration: the
`match(field, 'token')` WHERE function tokenizes string field values;
shard-persistent text indexes layer on top of this in the logstore round.
"""

from __future__ import annotations

import ctypes

import numpy as np

_LIB = None
_TRIED = False


def _bind(lib) -> None:
    lib.ogt_text_index_new.restype = ctypes.c_void_p
    lib.ogt_text_index_free.argtypes = [ctypes.c_void_p]
    lib.ogt_text_index_add.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_char_p, ctypes.c_int64
    ]
    lib.ogt_text_index_search.restype = ctypes.c_int64
    lib.ogt_text_index_search.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_int64,
    ]
    lib.ogt_text_index_tokens.restype = ctypes.c_int64
    lib.ogt_text_index_tokens.argtypes = [ctypes.c_void_p]


def _load():
    global _LIB, _TRIED
    if not _TRIED:
        _TRIED = True
        from opengemini_tpu import native

        _LIB = native.open_library("textindex", _bind)
    return _LIB


class TextIndex:
    """Inverted token index over documents; C++ when built, dict fallback."""

    def __init__(self) -> None:
        self._lib = _load()
        if self._lib is not None:
            self._h = self._lib.ogt_text_index_new()
        else:
            self._post: dict[str, list[int]] = {}

    def add(self, doc_id: int, text: str) -> None:
        if self._lib is not None:
            b = text.encode("utf-8", errors="replace")
            self._lib.ogt_text_index_add(self._h, doc_id, b, len(b))
        else:
            for tok in set(tokenize(text)):
                self._post.setdefault(tok, []).append(doc_id)

    def search(self, token: str) -> np.ndarray:
        """Doc ids matching a term. Multi-gram terms (CJK strings, mixed
        script) intersect their grams' postings — the per-character index
        scheme query_grams() documents. ASCII lowercases; non-ASCII is
        byte-exact (the index never case-folds it)."""
        grams = query_grams(token)
        if len(grams) > 1:
            out = None
            for g in grams:
                if g.isascii():
                    continue  # ASCII fragments may sit inside longer tokens
                ids = set(self.search(g).tolist())
                out = ids if out is None else out & ids
            if out is None:  # pure-ASCII multi-token term: all must match
                for g in grams:
                    ids = set(self.search(g).tolist())
                    out = ids if out is None else out & ids
            return np.asarray(sorted(out or ()), dtype=np.int64)
        token = token.lower() if token.isascii() else token
        if self._lib is not None:
            b = token.encode("utf-8", errors="replace")
            cap = 1024
            while True:
                out = np.empty(cap, dtype=np.int64)
                n = self._lib.ogt_text_index_search(self._h, b, len(b),
                                                    out.ctypes.data, cap)
                if n <= cap:
                    return out[:n].copy()
                cap = int(n)
        return np.asarray(sorted(self._post.get(token, [])), dtype=np.int64)

    def token_count(self) -> int:
        if self._lib is not None:
            return int(self._lib.ogt_text_index_tokens(self._h))
        return len(self._post)

    def close(self) -> None:
        if self._lib is not None and self._h:
            self._lib.ogt_text_index_free(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001
            pass


def tokenize(text: str) -> list[str]:
    """ASCII alnum runs >= 2 chars lowercased, plus one gram per
    non-ASCII character (reference SimpleGramTokenizer's split-table
    walk, FullTextIndex.cpp:19-40 — CJK indexes per character). Matches
    the C++ tokenizer byte-for-byte over utf-8 input."""
    out: list[str] = []
    cur: list[str] = []
    for ch in text:
        if ch.isascii():
            if ch.isalnum():
                cur.append(ch.lower())
                continue
            if len(cur) >= 2:
                out.append("".join(cur))
            cur = []
        else:
            if len(cur) >= 2:
                out.append("".join(cur))
            cur = []
            out.append(ch)
    if len(cur) >= 2:
        out.append("".join(cur))
    return out


def query_grams(term: str) -> list[str]:
    """Index lookup tokens for one match() search term: its own
    tokenization (a multi-character CJK term becomes several grams that
    the caller intersects)."""
    return tokenize(term)


def match_token(values: np.ndarray, valid: np.ndarray, token: str) -> np.ndarray:
    """Row mask for WHERE match(f, 'term').

    ASCII terms match whole tokens case-insensitively. Terms with
    non-ASCII characters match as EXACT (byte) substrings — the index
    never case-folds non-ASCII (neither does the reference's
    SimpleGramTokenizer), so the row filter must agree or pruning would
    silently drop rows the filter accepts."""
    has_cjk = not token.isascii()
    term = token if has_cjk else token.lower()
    out = np.zeros(len(values), dtype=np.bool_)
    for i, v in enumerate(values):
        if not (valid[i] and isinstance(v, str)):
            continue
        if has_cjk:
            out[i] = term in v
        else:
            out[i] = term in tokenize(v)
    return out
