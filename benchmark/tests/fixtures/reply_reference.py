"""A reference that a later configuration might bring for traffic with reply
legs (`benchmark/references/<name>.py`): the first deployments' reference,
and what a reply lane has to say.  It reads the lane labels of its generator
(`reply_flows.py`): `leg` 1 marks a reply, `frontend_ip`/`frontend_port` the
address its connection's client sent to.

On a reply lane the program answers from the connection's reply entry:
`reply` 1, `est` 1, ALLOW, not committed, no SNAT mark, no reject, the
Service of the frontend (-1 for a connection to a pod itself), and
`dnat_ip`/`dnat_port` equal to the frontend: the un-DNAT, the reply's source
restored to the address the client sent to.  Where the program's
direct-mapped flow cache lost that entry (another flow took its slot, a miss
that `remiss_share` counts), the reply is a packet of its own: `reply` 0 and
the first deployments' statements, as on every other lane.
"""
import numpy as np

import correct
import reference

REPLY = 1


class Reference(reference.Reference):
    def failed_statements(self, s: dict) -> dict:
        bad = correct.failed_statements(self, s)
        answered = (np.asarray(s["leg"]) == REPLY) & (
            np.asarray(s["reply"], np.int64) == 1)
        front_ip = correct._u32(s["frontend_ip"])
        front_port = np.asarray(s["frontend_port"], np.int64)
        svc, _ = self.resolve(front_ip, np.asarray(s["proto"], np.int64),
                              front_port)

        def differs(field, want):
            return np.asarray(s[field], np.int64) != want

        on_reply = {
            "service": differs("svc_idx", svc),
            "dnat": (correct._u32(s["dnat_ip"]) != front_ip)
            | differs("dnat_port", front_port),
            "code": differs("code", correct.ALLOW),
            "rule": np.zeros(len(answered), bool),  # the connection's own
            "reject_kind": differs("reject_kind", 0),
            "marks": differs("snat", 0),
            "est": differs("est", 1),
            "committed": differs("committed", 0),
        }
        for name, v in on_reply.items():
            bad[name] = np.where(answered, v, bad[name])
        s["ref_code"] = np.where(answered, correct.ALLOW, s["ref_code"])
        s["ref_rule"] = np.where(answered, None, s["ref_rule"])
        return bad
