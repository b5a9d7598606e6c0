"""The plain reference: the deployment's semantics written down a second
time, from the wire contract, importing nothing of ``tendermint_tpu``.

``cryptography`` (OpenSSL) does the one ed25519 verification per row;
everything else is ``hashlib`` and ``struct``.
"""
