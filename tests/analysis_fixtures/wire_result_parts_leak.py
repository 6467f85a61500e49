# Fixture: secret material handed to the result path that sends its payload
# as a view (head and header, then the array's bytes).  Parsed by
# repro.analysis in tests — never imported or executed.
from repro.runtime import wire


def result_of_secrets(sess, rid, res):
    core = sess.morpher.core
    return wire.encode_result_parts(rid, core)


def head_naming_perm(sess, rid, n):
    return wire.frame_head(2, {"rid": rid, "perm": list(sess.morpher.perm)}, n)


def fine(rid, res):
    return wire.encode_result_parts(rid, res)
