"""Equal requests on equal cache contents attach the same pages.

What a cached asking reads is part of what it computes: a page's last
bits depend on the dispatch that wrote it, and a routed model turns last
bits into another expert and another token (PERF.md, PR 27). So which
page an admission attaches must follow from the cache's contents, never
from the order in which blocks happened to be inserted or evicted.
"""

from dynamo_exp_tpu.engine.kv_manager import KvPageManager
from dynamo_exp_tpu.kv import PrefixIndex
from dynamo_exp_tpu.tokens import compute_block_hashes_for_seq

PS = 4


def _two_replies(index: PrefixIndex, first: int, second: int):
    """A prompt block (hash 1) and two blocks that extend its tail
    ``(9, 9, 9)`` and differ in their last token: two replies to one
    prompt, inserted in the order given."""
    index.insert(None, 1, tokens=(1, 2, 3, 4))
    for h in (first, second):
        index.insert(1, h, tokens=(9, 9, 9, h))


def test_partial_match_among_equal_candidates_is_a_rule_of_their_content():
    tail = (9, 9, 9)
    a, b = PrefixIndex(), PrefixIndex()
    _two_replies(a, 70, 50)
    _two_replies(b, 50, 70)
    # Whichever reply was registered first, the same block answers.
    assert a.partial_match(1, tail) == b.partial_match(1, tail) == (50, 3)
    # An eviction elsewhere (another chain's block) changes nothing ...
    a.insert(None, 200, tokens=(5, 5, 5, 5))
    a.insert(200, 201, tokens=(6, 6, 6, 6))
    a.remove(200)
    assert a.partial_match(1, tail) == (50, 3)
    # ... nor does evicting a candidate and registering it again, which
    # moves it to the end of the children's insertion order.
    a.remove(50)
    assert a.partial_match(1, tail) == (70, 3)
    a.insert(1, 50, tokens=(9, 9, 9, 50))
    assert a.partial_match(1, tail) == (50, 3)
    # A block that does not extend the tail is no candidate.
    a.insert(1, 10, tokens=(9, 9, 8, 1))
    assert a.partial_match(1, tail) == (50, 3)
    assert a.partial_match(1, (9, 9, 8)) == (10, 3)
    assert a.partial_match(1, (7,)) is None


def _serve(kv: KvPageManager, prompt: list[int], reply: list[int]) -> list[int]:
    """One asking through the manager as the scheduler drives it:
    admit, fill, make the shared tail private before the first decode
    write, register pages as the reply completes them, release. Returns
    the pages the admission attached (not its fresh ones)."""
    alloc = kv.allocate_sequence(prompt, 64, "r")
    assert alloc is not None
    pages = list(alloc.page_ids)
    attached = pages[: alloc.cached_pages] + ([alloc.shared_tail[0]] if alloc.shared_tail else [])
    kv.mark_filled(pages)
    if alloc.shared_tail:
        new = kv.make_private(alloc.shared_tail[0])
        if new != alloc.shared_tail[0]:
            kv.release_sequence([alloc.shared_tail[0]])
            pages[-1] = new
    tokens = prompt + reply
    while len(pages) * PS < len(tokens):
        pages.append(kv.allocate_page())
    hashes = compute_block_hashes_for_seq(tokens, PS)
    for i in range(alloc.cached_pages, len(tokens) // PS):
        kv.register_full_page(
            pages[i], hashes[i], hashes[i - 1] if i else None,
            tokens=tokens[i * PS : (i + 1) * PS],
        )
    kv.release_sequence(pages)
    return attached


def test_after_a_root_first_eviction_a_second_and_a_third_asking_attach_the_same_pages():
    """A finished sequence parks its pages in page order, so the LRU
    gives up a chain root first and the later pages stay registered but
    unreachable. The asking that follows recomputes the whole prompt
    (nothing matches from the root); the askings after it must then
    agree with one another on every page they attach, whichever mixture
    of recomputed and surviving pages that is."""
    kv = KvPageManager(num_pages=16, page_size=PS)
    prompt = list(range(100, 114))  # three full pages and a tail of two
    reply = [7, 8, 9, 10]
    first = _serve(kv, prompt, reply)
    assert first == []
    warm = _serve(kv, prompt, reply)
    assert len(warm) == 4  # three full pages and the shared tail
    # Take the free pages and then the LRU's two oldest: the chain's root.
    held = []
    while kv._free:
        held.append(kv.allocate_page())
    held += [kv.allocate_page(), kv.allocate_page()]
    assert kv.match_prefix(prompt)[0] == []
    kv.release_sequence(held)
    assert _serve(kv, prompt, reply) == []  # the recompute
    second = _serve(kv, prompt, reply)
    third = _serve(kv, prompt, reply)
    assert len(second) == 4 and second == third
    assert not kv.ledger_check()
