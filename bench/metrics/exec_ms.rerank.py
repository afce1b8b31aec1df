"""Wall time of the model stage per request it handled, in ms: host
packing, dispatch and the device's answer (``StageStats.busy_s /
events``)."""


def read(w):
    st = w.stage("rerank")
    return 1e3 * st.busy_s / st.events if st and st.events else None
