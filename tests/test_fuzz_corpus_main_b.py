"""Fixed-seed main fuzz corpus, chunks 4-6 (the
generator and the check live in :mod:`fuzz_corpus`)."""
import pytest

from fuzz_corpus import CHUNKS, run_corpus_chunk


@pytest.mark.parametrize("chunk", range(4, CHUNKS))
def test_fuzz_differential_corpus(chunk):
    run_corpus_chunk("main", chunk)
