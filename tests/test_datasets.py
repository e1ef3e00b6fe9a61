"""The synthetic key sets are reproducible from (name, n, seed) alone."""


def test_sosd_like_is_reproducible_across_processes():
    """The generators seed from (name, seed) alone: a salted str hash()
    would change these keys from one interpreter to the next."""
    import zlib

    from repro.data.datasets import sosd_like
    keys = sosd_like("wiki", 10_000, 0)
    assert (len(keys), int(keys[0]), int(keys[-1])) == (9226, 3, 61690)
    assert zlib.crc32(keys.tobytes()) == 3686618619
