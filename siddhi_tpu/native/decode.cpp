// Native Event builder: the fused drain's host decode of one micro-batch.
//
// One call turns a segment's lane arrays (read through the buffer protocol)
// into the list of `Event(timestamp, data)` objects that
// `core/event.py` `events_from_arrays` builds in Python: the same type, the
// same values, made eagerly. A lane may be strided and unaligned: the drain
// hands in views of the packed readback buffer, a row's lanes side by side.
// What C saves is the interpreter-level glue (the per-column lists, the
// (ts, data) pair `zip` makes only for `tuple.__new__` to copy, a `partial`
// call per row) and, with `untrack`, the collector's pass over objects that
// can be in no cycle: `Event` is a namedtuple subclass, and CPython untracks
// only exact tuples.
//
// Called through ctypes.PyDLL, so the GIL is held from entry to return and
// the interner's table cannot change under the loop.

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <cstdint>
#include <cstring>

namespace {

// how a lane's element becomes a value (core/event.py `_native_lanes`)
enum LaneKind {
    LANE_INT = 0,    // int32 / int64; the null sentinel -> None
    LANE_FLOAT = 1,  // float32 (widened) / float64; NaN -> None
    LANE_BOOL = 2,   // one byte; non-zero -> True
    LANE_ID = 3,     // int32 / int64 index into the interner's id table
};

struct Lane {
    int kind;
    Py_ssize_t width;
    Py_ssize_t stride;
    const char* data;
    long long null;
    PyObject* table;  // borrowed; LANE_ID only
    Py_buffer view;
};

// a one-dimensional buffer of at least n items, any stride
bool acquire(PyObject* arr, Py_buffer* view, Py_ssize_t n, const char* what) {
    if (PyObject_GetBuffer(arr, view, PyBUF_STRIDES) != 0) return false;
    if (view->ndim != 1 || view->shape[0] < n) {
        PyErr_Format(PyExc_ValueError,
                     "%s lane: %d dimensions, %zd items where %zd rows are "
                     "asked for", what, view->ndim,
                     view->ndim ? view->shape[0] : (Py_ssize_t)0, n);
        PyBuffer_Release(view);
        return false;
    }
    return true;
}

template <typename T>
inline T load(const char* at) {
    T v;
    std::memcpy(&v, at, sizeof(T));  // a lane of a packed row is unaligned
    return v;
}

inline long long load_int(const Lane& lane, Py_ssize_t r) {
    const char* at = lane.data + r * lane.stride;
    return lane.width == 4 ? (long long)load<int32_t>(at)
                           : (long long)load<int64_t>(at);
}

inline PyObject* lane_value(const Lane& lane, Py_ssize_t r) {
    switch (lane.kind) {
    case LANE_INT: {
        long long v = load_int(lane, r);
        if (v == lane.null) Py_RETURN_NONE;
        return PyLong_FromLongLong(v);
    }
    case LANE_FLOAT: {
        const char* at = lane.data + r * lane.stride;
        double v = lane.width == 4 ? (double)load<float>(at)
                                   : load<double>(at);
        if (v != v) Py_RETURN_NONE;
        return PyFloat_FromDouble(v);
    }
    case LANE_BOOL:
        if (lane.data[r * lane.stride]) Py_RETURN_TRUE;
        Py_RETURN_FALSE;
    default: {
        long long id = load_int(lane, r);
        if (id < 0 || id >= PyList_GET_SIZE(lane.table)) {
            PyErr_Format(PyExc_IndexError,
                         "interned id %lld out of range", id);
            return nullptr;
        }
        PyObject* v = PyList_GET_ITEM(lane.table, (Py_ssize_t)id);
        Py_INCREF(v);
        return v;
    }
    }
}

}  // namespace

// event_type: the Event class (a tuple subclass of two fields);
// ts: int64 lane; lanes: tuple of (kind, array, null, table-or-None) in
// schema order; n: rows; untrack: every attribute is atomic, so neither the
// Event nor its data tuple can be in a reference cycle.
extern "C" PyObject* siddhi_build_events(PyObject* event_type, PyObject* ts,
                                         PyObject* lanes, Py_ssize_t n,
                                         int untrack) {
    if (!PyType_Check(event_type) ||
        !PyType_IsSubtype((PyTypeObject*)event_type, &PyTuple_Type) ||
        !PyTuple_Check(lanes)) {
        PyErr_SetString(PyExc_TypeError,
                        "build_events(tuple subclass, ts, tuple of lanes, n)");
        return nullptr;
    }
    PyTypeObject* etype = (PyTypeObject*)event_type;
    const Py_ssize_t width = PyTuple_GET_SIZE(lanes);
    if (n < 0) n = 0;

    Py_buffer ts_view;
    if (!acquire(ts, &ts_view, n, "timestamp")) return nullptr;
    Lane* lane = width ? (Lane*)PyMem_Malloc(width * sizeof(Lane)) : nullptr;
    Py_ssize_t held = 0;
    PyObject* out = nullptr;

    if (ts_view.itemsize != 8 || (width && lane == nullptr)) {
        if (ts_view.itemsize != 8)
            PyErr_SetString(PyExc_TypeError, "timestamp lane is not 64-bit");
        else
            PyErr_NoMemory();
        goto done;
    }
    for (; held < width; held++) {
        PyObject* spec = PyTuple_GET_ITEM(lanes, held);
        Lane& l = lane[held];
        if (!PyTuple_Check(spec) || PyTuple_GET_SIZE(spec) != 4) {
            PyErr_SetString(PyExc_TypeError,
                            "a lane is (kind, array, null, table)");
            goto done;
        }
        l.kind = (int)PyLong_AsLong(PyTuple_GET_ITEM(spec, 0));
        l.null = PyLong_AsLongLong(PyTuple_GET_ITEM(spec, 2));
        l.table = PyTuple_GET_ITEM(spec, 3);
        if (PyErr_Occurred()) goto done;
        if (l.kind < LANE_INT || l.kind > LANE_ID ||
            (l.kind == LANE_ID && !PyList_Check(l.table))) {
            PyErr_SetString(PyExc_TypeError, "unknown lane kind or table");
            goto done;
        }
        if (!acquire(PyTuple_GET_ITEM(spec, 1), &l.view, n, "attribute"))
            goto done;
        l.width = l.view.itemsize;
        l.stride = l.view.strides[0];
        l.data = (const char*)l.view.buf;
        if (l.kind == LANE_BOOL ? l.width != 1
                                : (l.width != 4 && l.width != 8)) {
            PyErr_Format(PyExc_TypeError,
                         "lane kind %d cannot read %zd-byte items",
                         l.kind, l.width);
            PyBuffer_Release(&l.view);
            goto done;
        }
    }

    out = PyList_New(n);
    if (out == nullptr) goto done;
    {
        const char* tsv = (const char*)ts_view.buf;
        const Py_ssize_t ts_stride = ts_view.strides[0];
        for (Py_ssize_t r = 0; r < n; r++) {
            PyObject* data = PyTuple_New(width);
            PyObject* stamp = data
                ? PyLong_FromLongLong(load<int64_t>(tsv + r * ts_stride))
                : nullptr;
            PyObject* ev = stamp ? etype->tp_alloc(etype, 2) : nullptr;
            if (ev == nullptr) {
                Py_XDECREF(stamp);
                Py_XDECREF(data);
                Py_CLEAR(out);
                goto done;
            }
            // the Event owns both from here: a failure below frees them
            // with the list
            PyTuple_SET_ITEM(ev, 0, stamp);
            PyTuple_SET_ITEM(ev, 1, data);
            PyList_SET_ITEM(out, r, ev);
            for (Py_ssize_t c = 0; c < width; c++) {
                PyObject* v = lane_value(lane[c], r);
                if (v == nullptr) {
                    Py_CLEAR(out);
                    goto done;
                }
                PyTuple_SET_ITEM(data, c, v);
            }
            if (untrack) {
                PyObject_GC_UnTrack(ev);
                PyObject_GC_UnTrack(data);
            }
        }
    }

done:
    for (Py_ssize_t c = 0; c < held; c++) PyBuffer_Release(&lane[c].view);
    PyMem_Free(lane);
    PyBuffer_Release(&ts_view);
    return out;
}
