# Fixture: secret material handed to the decode lane's frames: a request
# frame and a result whose scores travel in the frame header.  Parsed by
# repro.analysis in tests — never imported or executed.
from repro.runtime import wire
from repro.runtime.api import DeliveryResult


def scores_of_secrets(sess, ids):
    perm = sess.morpher.perm
    return DeliveryResult(0, "t", "decode", "tokens", 0, ids, 0.0, 0.0, 0,
                          scores=perm)


def prompt_of_secrets(sess, rid):
    return wire.encode_request(sess.morpher.inv_perm, rid)


def fine(rid, res, ids, logits):
    out = DeliveryResult(0, "t", "decode", "tokens", 0, ids, 0.0, 0.0, 0,
                         scores=logits)
    return wire.encode_result_parts(rid, out)
