"""Cryptographic peer identities for the swarm plane.

The reference inherits this from libp2p (peer ids derived from keypairs) and
hivemind's RSASignatureValidator (signed per-peer DHT subkey records,
src/petals/cli/run_dht.py + hivemind dht/validation.py behavior). This build
implements the same guarantees on Ed25519:

- a PeerID is the SHA-256 of the node's Ed25519 public key — you cannot claim
  an id you don't hold the private key for;
- RPC hellos are challenge/response: each side signs the other's nonce, so a
  connection's remote_peer_id is only set when PROVEN;
- per-peer DHT announcements (subkey records) are signed over a canonical
  form of (uid, subkey, payload, expiration); storers and readers both verify
  and reject records whose subkey doesn't match the verified writer.
"""

from __future__ import annotations

import hashlib
import json
from typing import Any, Optional

try:
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
        Ed25519PublicKey,
    )
except ModuleNotFoundError:  # hosts without `cryptography`: RFC 8032 in Python
    from petals_tpu.dht._ed25519_fallback import (
        Ed25519PrivateKey,
        Ed25519PublicKey,
        InvalidSignature,
    )

from petals_tpu.data_structures import PeerID

_HELLO_CONTEXT = b"ptu-hello-v1|"
_ANNOUNCE_CONTEXT = b"ptu-announce-v1|"


class Identity:
    """An Ed25519 keypair whose public-key hash IS the peer id."""

    __slots__ = ("_private", "_public_bytes", "_peer_id")

    def __init__(self, private: Ed25519PrivateKey):
        self._private = private
        self._public_bytes = private.public_key().public_bytes_raw()
        self._peer_id = peer_id_of(self._public_bytes)

    @classmethod
    def generate(cls) -> "Identity":
        return cls(Ed25519PrivateKey.generate())

    @classmethod
    def from_seed(cls, seed: bytes) -> "Identity":
        """Deterministic identity (test swarms with stable multiaddrs,
        reference tests/bootstrap.id pattern)."""
        return cls(Ed25519PrivateKey.from_private_bytes(hashlib.sha256(seed).digest()))

    @property
    def peer_id(self) -> PeerID:
        return self._peer_id

    @property
    def public_bytes(self) -> bytes:
        return self._public_bytes

    def sign(self, message: bytes) -> bytes:
        return self._private.sign(message)


def peer_id_of(public_bytes: bytes) -> PeerID:
    return PeerID(hashlib.sha256(public_bytes).digest())


def verify(public_bytes: bytes, signature: bytes, message: bytes) -> bool:
    try:
        Ed25519PublicKey.from_public_bytes(public_bytes).verify(signature, message)
        return True
    except (InvalidSignature, ValueError, TypeError):
        return False


# ------------------------------------------------------------------ hello auth


def hello_challenge_message(
    signer_public: bytes, peer_public: bytes, peer_nonce: bytes
) -> bytes:
    """What a node signs to prove its identity to ``peer``: its OWN public key
    bound together with the peer's key and nonce. Binding the signer's key is
    what stops a man-in-the-middle from relaying an honest peer's proof as its
    own (the relayed signature never verifies against the attacker's key)."""
    return _HELLO_CONTEXT + signer_public + b"|" + peer_public + peer_nonce


# ------------------------------------------------------------------ announcements


def announce_message(uid: str, subkey: str, payload: Any, expiration: float) -> bytes:
    """Canonical signing form of one DHT announcement. Uses sorted-key JSON of
    msgpack-safe plain types so writer and verifier serialize identically."""
    body = json.dumps(
        {"uid": uid, "subkey": subkey, "payload": payload, "exp": round(float(expiration), 3)},
        sort_keys=True,
        separators=(",", ":"),
        ensure_ascii=True,
    )
    return _ANNOUNCE_CONTEXT + body.encode()


def sign_announcement(
    identity: Identity, uid: str, payload: Any, expiration: float
) -> dict:
    """Wrap ``payload`` in a signed record for subkey = our peer id."""
    subkey = identity.peer_id.to_string()
    message = announce_message(uid, subkey, payload, expiration)
    return {
        "uid": uid,
        "payload": payload,
        "pub": identity.public_bytes.hex(),
        "sig": identity.sign(message).hex(),
    }


def verify_announcement(value: Any, subkey: Optional[str], expiration: float) -> bool:
    """True iff ``value`` is a well-formed signed record whose signature is
    valid AND whose signer's key hashes to ``subkey`` — nobody can overwrite
    another peer's announcements."""
    if not isinstance(value, dict) or subkey is None:
        return False
    try:
        public_bytes = bytes.fromhex(value["pub"])
        signature = bytes.fromhex(value["sig"])
        uid = value["uid"]
        payload = value["payload"]
    except (KeyError, TypeError, ValueError):
        return False
    if peer_id_of(public_bytes).to_string() != subkey:
        return False
    message = announce_message(uid, subkey, payload, expiration)
    return verify(public_bytes, signature, message)
