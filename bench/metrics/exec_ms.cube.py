"""Host time of the cube-fetch stage per request it handled, in ms
(``StageStats.busy_s / events``)."""


def read(w):
    st = w.stage("cube")
    return 1e3 * st.busy_s / st.events if st and st.events else None
