/* Send-mode closure + matched probe + cancel:
 * Issend/Ibsend/Irsend, Bsend/Rsend, Buffer_attach/detach,
 * Mprobe/Improbe/Mrecv/Imrecv, Cancel/Test_cancelled,
 * Status_set_elements/cancelled. References:
 * ompi/mpi/c/issend.c.in, ibsend.c.in, mprobe.c.in, imrecv.c.in,
 * cancel.c.in, status_set_elements.c.in. */
#include <mpi.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>

static int rank, size;

#define CHECK(cond, code)                                            \
    do {                                                             \
        if (!(cond)) {                                               \
            fprintf(stderr, "rank %d: check failed at line %d\n",    \
                    rank, __LINE__);                                 \
            MPI_Abort(MPI_COMM_WORLD, code);                         \
        }                                                            \
    } while (0)

int main(int argc, char **argv)
{
    MPI_Init(&argc, &argv);
    MPI_Comm_rank(MPI_COMM_WORLD, &rank);
    MPI_Comm_size(MPI_COMM_WORLD, &size);
    CHECK(size >= 2, 1);

    /* ---- buffered sends: attach, Bsend + Ibsend, detach --------- */
    int bufsz = 4 * (1024 + MPI_BSEND_OVERHEAD);
    char *bbuf = malloc(bufsz);
    CHECK(MPI_Buffer_attach(bbuf, bufsz) == MPI_SUCCESS, 2);

    if (rank == 0) {
        double x[8] = {1, 2, 3, 4, 5, 6, 7, 8};
        CHECK(MPI_Bsend(x, 8, MPI_DOUBLE, 1, 10, MPI_COMM_WORLD)
              == MPI_SUCCESS, 3);
        MPI_Request r;
        CHECK(MPI_Ibsend(x, 4, MPI_DOUBLE, 1, 11, MPI_COMM_WORLD, &r)
              == MPI_SUCCESS, 4);
        MPI_Wait(&r, MPI_STATUS_IGNORE);
        /* Issend completes only on matched receive */
        CHECK(MPI_Issend(x, 2, MPI_DOUBLE, 1, 12, MPI_COMM_WORLD, &r)
              == MPI_SUCCESS, 5);
        int flag = -1;
        MPI_Status st;
        MPI_Wait(&r, &st);               /* blocks until 1 receives */
        /* rsend: the partner guaranteed the recv is posted (it posted
         * before raising tag-13's flag via a ssend handshake) */
        MPI_Recv(&flag, 1, MPI_INT, 1, 13, MPI_COMM_WORLD,
                 MPI_STATUS_IGNORE);
        CHECK(MPI_Rsend(x, 3, MPI_DOUBLE, 1, 14, MPI_COMM_WORLD)
              == MPI_SUCCESS, 6);
        MPI_Request rr;
        CHECK(MPI_Irsend(x, 3, MPI_DOUBLE, 1, 15, MPI_COMM_WORLD, &rr)
              == MPI_SUCCESS, 7);
        MPI_Wait(&rr, MPI_STATUS_IGNORE);
    } else if (rank == 1) {
        double y[8];
        MPI_Recv(y, 8, MPI_DOUBLE, 0, 10, MPI_COMM_WORLD,
                 MPI_STATUS_IGNORE);
        CHECK(y[7] == 8.0, 8);
        MPI_Recv(y, 4, MPI_DOUBLE, 0, 11, MPI_COMM_WORLD,
                 MPI_STATUS_IGNORE);
        MPI_Recv(y, 2, MPI_DOUBLE, 0, 12, MPI_COMM_WORLD,
                 MPI_STATUS_IGNORE);
        MPI_Request pre[2];
        MPI_Irecv(y, 3, MPI_DOUBLE, 0, 14, MPI_COMM_WORLD, &pre[0]);
        MPI_Irecv(y + 3, 3, MPI_DOUBLE, 0, 15, MPI_COMM_WORLD,
                  &pre[1]);
        int one = 1;
        MPI_Send(&one, 1, MPI_INT, 0, 13, MPI_COMM_WORLD);
        MPI_Waitall(2, pre, MPI_STATUSES_IGNORE);
        CHECK(y[0] == 1.0 && y[3] == 1.0, 9);
    }

    int detsz = 0;
    void *detbuf = NULL;
    CHECK(MPI_Buffer_detach(&detbuf, &detsz) == MPI_SUCCESS, 10);
    CHECK(detbuf == (void *)bbuf && detsz == bufsz, 11);
    free(bbuf);

    /* ---- matched probe: Mprobe/Mrecv, Improbe/Imrecv ------------ */
    if (rank == 0) {
        int a = 41, b = 42;
        MPI_Send(&a, 1, MPI_INT, 1, 20, MPI_COMM_WORLD);
        MPI_Send(&b, 1, MPI_INT, 1, 21, MPI_COMM_WORLD);
    } else if (rank == 1) {
        MPI_Message msg;
        MPI_Status st;
        CHECK(MPI_Mprobe(0, 20, MPI_COMM_WORLD, &msg, &st)
              == MPI_SUCCESS, 12);
        CHECK(msg != MPI_MESSAGE_NULL, 13);
        int cnt = -1;
        MPI_Get_count(&st, MPI_INT, &cnt);
        CHECK(cnt == 1 && st.MPI_TAG == 20, 14);
        int got = -1;
        CHECK(MPI_Mrecv(&got, 1, MPI_INT, &msg, &st) == MPI_SUCCESS,
              15);
        CHECK(got == 41 && msg == MPI_MESSAGE_NULL, 16);

        int flag = 0;
        MPI_Message msg2 = MPI_MESSAGE_NULL;
        for (int spin = 0; spin < 20000 && !flag; spin++)
            CHECK(MPI_Improbe(0, 21, MPI_COMM_WORLD, &flag, &msg2, &st)
                  == MPI_SUCCESS, 17);
        CHECK(flag && msg2 != MPI_MESSAGE_NULL, 18);
        MPI_Request r;
        CHECK(MPI_Imrecv(&got, 1, MPI_INT, &msg2, &r) == MPI_SUCCESS,
              19);
        MPI_Wait(&r, &st);
        CHECK(got == 42, 20);
    }

    /* ---- cancel a receive that can never match ------------------ */
    {
        int never;
        MPI_Request r;
        MPI_Irecv(&never, 1, MPI_INT, rank == 0 ? 1 : 0, 999,
                  MPI_COMM_WORLD, &r);
        CHECK(MPI_Cancel(&r) == MPI_SUCCESS, 21);
        MPI_Status st;
        MPI_Wait(&r, &st);
        int cancelled = 0;
        CHECK(MPI_Test_cancelled(&st, &cancelled) == MPI_SUCCESS, 22);
        CHECK(cancelled, 23);
    }

    /* ---- status setters (generalized-request toolkit) ----------- */
    {
        MPI_Status st;
        memset(&st, 0, sizeof(st));
        CHECK(MPI_Status_set_elements(&st, MPI_DOUBLE, 3)
              == MPI_SUCCESS, 24);
        int cnt = -1;
        MPI_Get_count(&st, MPI_DOUBLE, &cnt);
        CHECK(cnt == 3, 25);
        int el = -1;
        MPI_Get_elements(&st, MPI_DOUBLE, &el);
        CHECK(el == 3, 26);
        CHECK(MPI_Status_set_cancelled(&st, 1) == MPI_SUCCESS, 27);
        int c = 0;
        MPI_Test_cancelled(&st, &c);
        CHECK(c == 1, 28);
    }

    /* ---- dynamic error space ------------------------------------ */
    {
        int cls = -1, code = -1;
        CHECK(MPI_Add_error_class(&cls) == MPI_SUCCESS, 29);
        CHECK(cls > MPI_ERR_LASTCODE || cls >= 64, 30);
        CHECK(MPI_Add_error_code(cls, &code) == MPI_SUCCESS, 31);
        CHECK(MPI_Add_error_string(code, "my custom failure")
              == MPI_SUCCESS, 32);
        char msg[MPI_MAX_ERROR_STRING];
        int len = 0;
        CHECK(MPI_Error_string(code, msg, &len) == MPI_SUCCESS, 33);
        CHECK(strcmp(msg, "my custom failure") == 0, 34);
        int ec = -1;
        CHECK(MPI_Error_class(code, &ec) == MPI_SUCCESS && ec == cls,
              35);
    }

    MPI_Barrier(MPI_COMM_WORLD);
    printf("OK c21_sendmodes rank=%d/%d\n", rank, size);
    MPI_Finalize();
    return 0;
}
