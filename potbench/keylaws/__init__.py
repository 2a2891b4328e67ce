"""Key laws, one file each, found by the name a mix gives under ``keys.law``.

A key law module defines ``draw(rng, n_keys, shape, params) -> ranks``:
``shape`` is ``(n_txns, keys_per_txn)``, ranks lie in ``[0, n_keys)`` and
are distinct within a row, and ``params`` is the mix's ``keys`` object.
The stream maps a rank to a key through a permutation drawn from the run's
seed, so the law fixes how often each rank is drawn, and the seed where
each rank lives in the store.
"""
